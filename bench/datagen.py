"""Seeded Criteo-format rows, encoded to UTF-8 with numpy alone.

The table follows ``repro.data.synth``'s semantics (label in {0, 1};
13 signed decimal counters, heavy-tailed, some negative, some empty; 26
hex hashes, some empty) with one change that the deployment needs: each
sparse column draws from its own published cardinality by a bounded
Zipf law, and the drawn rank is hashed to 32 bits. The configuration
file names the cardinalities and every assumed parameter.

Encoding is vectorized: every field is written into a fixed-width byte
matrix and a mask selects the bytes that exist, so a million rows encode
in seconds. The bytes equal what ``synth.encode_utf8`` writes for the
same table (``bench/tests/test_bench_datagen.py``).
"""

from __future__ import annotations

import numpy as np

TAB, NEWLINE, MINUS = 0x09, 0x0A, 0x2D
DIGITS = np.frombuffer(b"0123456789abcdef", np.uint8)
DENSE_WIDTH = 11  # "-" and the 10 digits of an int32
HEX_WIDTH = 8
HASH_KEY = 0x5EED
# Upper bound of the mean encoded row length, used to size a first draw.
ROW_BYTES_GUESS = 240


def _splitmix64(x: np.ndarray) -> np.ndarray:
    x = x + np.uint64(0x9E3779B97F4A7C15)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def zipf_ranks(rng: np.random.Generator, n: int, cardinality: int, s: float) -> np.ndarray:
    """``n`` ranks in [1, cardinality] from a bounded Zipf law of exponent
    ``s``: the continuous law on [1, cardinality + 1), floored."""
    u = rng.random(n)
    a = 1.0 - s
    top = float(cardinality + 1) ** a
    r = (1.0 + u * (top - 1.0)) ** (1.0 / a)
    return np.clip(np.floor(r), 1, cardinality).astype(np.int64)


def make_table(cfg: dict, rows: int, seed: int) -> dict[str, np.ndarray]:
    """The ground-truth table: int32 ``label [R]``, ``dense [R, 13]``,
    ``sparse [R, 26]`` (the uint32 hash as int32; empties are 0) and the
    two emptiness masks."""
    a = cfg["assumed"]
    rng = np.random.default_rng(seed)
    cards = cfg["cardinalities"]
    n_dense = cfg["schema"]["n_dense"]

    label = rng.integers(0, 2, size=rows, dtype=np.int32)
    dense = rng.exponential(a["dense_scale"], size=(rows, n_dense)).astype(np.int64)
    dense = np.where(rng.random((rows, n_dense)) < a["p_negative"], -dense, dense)
    dense_empty = rng.random((rows, n_dense)) < a["p_empty_dense"]
    dense = np.where(dense_empty, 0, dense).astype(np.int32)

    # The same category hashes to the same value in every seed's data (as
    # one click log's days share their ids); the seed draws which occur.
    key = _splitmix64(np.uint64(HASH_KEY) + np.arange(len(cards), dtype=np.uint64))
    sparse = np.empty((rows, len(cards)), np.uint32)
    for c, card in enumerate(cards):
        ranks = zipf_ranks(rng, rows, int(card), a["zipf_exponent"]).astype(np.uint64)
        sparse[:, c] = (_splitmix64(ranks ^ key[c]) & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    sparse_empty = rng.random(sparse.shape) < a["p_empty_sparse"]
    sparse = np.where(sparse_empty, np.uint32(0), sparse)
    return {
        "label": label,
        "dense": dense,
        "sparse": sparse.view(np.int32),
        "dense_empty": dense_empty,
        "sparse_empty": sparse_empty,
    }


def _decimal_field(x: np.ndarray, empty: np.ndarray):
    """Left-aligned chars ``[..., DENSE_WIDTH]`` and lengths of signed ints."""
    v = np.abs(x.astype(np.int64))
    n = 1 + sum((v >= 10**k).astype(np.int64) for k in range(1, 10))
    neg = (x < 0).astype(np.int64)
    chars = np.zeros(x.shape + (DENSE_WIDTH,), np.uint8)
    for p in range(DENSE_WIDTH):
        k = p - neg  # digit index at this position
        exp = np.clip(n - 1 - k, 0, 9)
        digit = (v // (10 ** exp)) % 10
        chars[..., p] = np.where(k >= 0, DIGITS[digit], MINUS)
    length = np.where(empty, 0, n + neg)
    return chars, length


def _hex_field(x: np.ndarray, empty: np.ndarray):
    """Left-aligned lowercase hex chars ``[..., HEX_WIDTH]`` and lengths."""
    v = x.view(np.uint32).astype(np.int64)
    n = 1 + sum((v >= 16**k).astype(np.int64) for k in range(1, 8))
    chars = np.zeros(x.shape + (HEX_WIDTH,), np.uint8)
    for p in range(HEX_WIDTH):
        shift = np.clip(n - 1 - p, 0, 7) * 4
        chars[..., p] = DIGITS[(v >> shift) & 15]
    length = np.where(empty, 0, n)
    return chars, length


def encode_utf8(table: dict[str, np.ndarray]) -> np.ndarray:
    """``label \\t dense... \\t sparse... \\n`` per row, as a uint8 array.

    Each field gets a slot of ``DENSE_WIDTH`` chars and one delimiter;
    a mask keeps the chars the field has and the delimiter."""
    rows = table["label"].shape[0]
    lab_chars, lab_len = _decimal_field(table["label"][:, None], np.zeros((rows, 1), bool))
    den_chars, den_len = _decimal_field(table["dense"], table["dense_empty"])
    hex_chars, hex_len = _hex_field(table["sparse"], table["sparse_empty"])
    n_dense = den_chars.shape[1]
    slot = DENSE_WIDTH + 1
    out = np.zeros((rows, 1 + n_dense + hex_chars.shape[1], slot), np.uint8)
    out[:, :1, :DENSE_WIDTH] = lab_chars
    out[:, 1 : 1 + n_dense, :DENSE_WIDTH] = den_chars
    out[:, 1 + n_dense :, :HEX_WIDTH] = hex_chars
    out[:, :, DENSE_WIDTH] = TAB
    out[:, -1, DENSE_WIDTH] = NEWLINE
    length = np.concatenate([lab_len, den_len, hex_len], axis=1)
    keep = np.arange(slot)[None, None, :] < length[:, :, None]
    keep[:, :, DENSE_WIDTH] = True
    return out[keep]


def frame_chunks(buf: np.ndarray, chunk_bytes: int, n_chunks: int):
    """Cut ``buf`` into row-aligned chunks of at most ``chunk_bytes``
    (each zero-padded to ``chunk_bytes``), as ``synth.chunk_stream`` does,
    and keep the first ``n_chunks``. Returns ``(chunks uint8 [n, chunk_bytes],
    rows per chunk, framed bytes per chunk)``, or None if ``buf`` holds
    fewer than ``n_chunks`` whole chunks."""
    ends = np.flatnonzero(buf == NEWLINE) + 1
    chunks = np.zeros((n_chunks, chunk_bytes), np.uint8)
    rows, nbytes = [], []
    start, row0 = 0, 0
    for i in range(n_chunks):
        k = int(np.searchsorted(ends, start + chunk_bytes, side="right"))
        if k <= row0:
            raise ValueError(f"a row is longer than chunk_bytes={chunk_bytes}")
        if k >= ends.size:  # the rows drawn may not fill this chunk
            return None
        end = int(ends[k - 1])
        chunks[i, : end - start] = buf[start:end]
        rows.append(k - row0)
        nbytes.append(end - start)
        start, row0 = end, k
    return chunks, np.asarray(rows, np.int64), np.asarray(nbytes, np.int64)


def make_job_data(cfg: dict, n_chunks: int, seed: int):
    """Rows of exactly ``n_chunks`` full chunks of the configuration's size.

    Returns ``(table, chunks, rows_per_chunk, bytes_per_chunk)``; ``table``
    holds exactly the rows the chunks carry, in order."""
    chunk_bytes = cfg["pipeline"]["chunk_bytes"]
    rows = n_chunks * chunk_bytes // ROW_BYTES_GUESS + 64
    while True:
        table = make_table(cfg, rows, seed)
        framed = frame_chunks(encode_utf8(table), chunk_bytes, n_chunks)
        if framed is not None:
            break
        rows *= 2
    chunks, rows_per_chunk, bytes_per_chunk = framed
    n = int(rows_per_chunk.sum())
    return {k: v[:n] for k, v in table.items()}, chunks, rows_per_chunk, bytes_per_chunk
