"""Adversarial decode-differential fuzzer: hostile UTF-8 chunks through
every decode path — the plain kernel, both bytes-in fused kernels, and
the engines — must agree with the reference scan bit-for-bit.

The hostile classes (one generator, shared by the hypothesis properties
and the always-on deterministic corpus):

  * truncated final rows (cut mid-field, mid-row, right at a delimiter);
  * empty fields and all-delimiter rows (FillMissing semantics);
  * overlong / invalid hex digits (>8 digits wraps like the register;
    non-hex bytes decode to whatever garbage the ref produces — the
    contract is agreement, not rejection);
  * interior / doubled minus signs, overlong decimals, stray bytes;
  * rows straddling tile boundaries (``block=256`` shrinks the byte
    tile so small buffers still cross carries);
  * more rows than ``max_rows`` (overflow rows must be dropped
    identically on every path).

``hypothesis`` is optional (tests/_hypothesis_fallback): without it the
property tests skip but the deterministic corpus below still pins every
class on every path.
"""

import dataclasses
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # optional dep — property tests skip, rest run
    from tests._hypothesis_fallback import given, settings, strategies as st

from repro.core import ops as core_ops
from repro.core import pipeline as pipeline_lib
from repro.core import vocab as vocab_lib
from repro.data import synth
from repro.kernels.decode_utf8 import ops as dops
from repro.kernels.decode_utf8 import ref as dref
from repro.kernels.fused_decode_vocab import ops as fdv_ops
from repro.kernels.fused_decode_xform import ops as fdx_ops

# Small byte tile: a ~20-byte row makes every ~13th row straddle a tile
# boundary, so tiny fuzz buffers still exercise the carry chain.
BLOCK = 256


# --------------------------------------------------------------------- #
# hostile chunk generator (plain numpy — shared by hypothesis + corpus)
# --------------------------------------------------------------------- #

_HEX = "0123456789abcdef"
ROW_KINDS = (
    "normal",
    "empty_fields",
    "all_delim",
    "invalid_hex",
    "overlong_hex",
    "overlong_decimal",
    "weird_minus",
    "long_straddle",
)


def _hostile_row(rng, kind: str, n_dense: int, n_sparse: int) -> bytes:
    """One tab-separated row (no newline) of the given hostile class."""
    label = [str(rng.integers(0, 2))]
    dense = [str(rng.integers(-99, 1000)) for _ in range(n_dense)]
    sparse = [
        "".join(rng.choice(list(_HEX), size=rng.integers(1, 9)))
        for _ in range(n_sparse)
    ]
    if kind == "empty_fields":
        for fields in (dense, sparse):
            for i in range(len(fields)):
                if rng.random() < 0.5:
                    fields[i] = ""
    elif kind == "all_delim":
        label, dense, sparse = [""], [""] * n_dense, [""] * n_sparse
    elif kind == "invalid_hex" and n_sparse:
        i = int(rng.integers(0, n_sparse))
        sparse[i] = "".join(
            rng.choice(list("ghijklmnopqrstuvwxyzGHIJKLZ!@"), size=4)
        )
    elif kind == "overlong_hex" and n_sparse:
        i = int(rng.integers(0, n_sparse))
        sparse[i] = "".join(rng.choice(list(_HEX), size=rng.integers(9, 17)))
    elif kind == "overlong_decimal" and n_dense:
        i = int(rng.integers(0, n_dense))
        dense[i] = str(rng.integers(10**10, 10**14))
    elif kind == "weird_minus" and n_dense:
        i = int(rng.integers(0, n_dense))
        dense[i] = rng.choice(["--7", "1-2", "-", "3-"])
    elif kind == "long_straddle" and n_sparse:
        i = int(rng.integers(0, n_sparse))
        sparse[i] = "".join(rng.choice(list(_HEX), size=BLOCK + 40))
    return "\t".join(label + dense + sparse).encode()


def _hostile_chunk(
    seed: int, n_dense: int, n_sparse: int, n_rows: int, truncate: int
) -> np.ndarray:
    """A padded hostile chunk; ``truncate`` > 0 cuts that many bytes off
    the final row (dropping its newline — the truncated-final-row case)."""
    rng = np.random.default_rng(seed)
    rows = [
        _hostile_row(rng, rng.choice(ROW_KINDS), n_dense, n_sparse)
        for _ in range(n_rows)
    ]
    raw = b"".join(r + b"\n" for r in rows)
    if truncate and rows:
        cut = min(truncate, len(rows[-1]) + 1)
        raw = raw[:-cut]
    return synth.pad_bytes(raw, multiple=BLOCK)


# --------------------------------------------------------------------- #
# the three differential assertions (kernel path vs reference scan)
# --------------------------------------------------------------------- #


def _assert_decode_agree(buf, n_dense, n_sparse, max_rows):
    """Plain decode kernel ≡ ``ref.decode_bytes``, full arrays."""
    n_fields = 1 + n_dense + n_sparse
    # the plain kernel's byte tile is fixed at 2048; re-pad (zero bytes
    # are inert — both sides see the identical buffer)
    buf = np.pad(np.asarray(buf), (0, (-len(buf)) % 2048))
    hex_t = jnp.arange(n_fields) >= 1 + n_dense
    kw = dict(
        n_fields=n_fields, max_rows=max_rows, n_dense=n_dense, n_sparse=n_sparse
    )
    got = dops.decode(jnp.asarray(buf), hex_t, **kw)
    want = dref.decode_bytes(jnp.asarray(buf), hex_t, **kw)
    for name, g, w in zip(("label", "dense", "sparse", "valid"), got, want):
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(w), err_msg=f"decode {name}"
        )


def _assert_vocab_agree(buf, n_dense, n_sparse, max_rows, vocab_range, offset):
    """Bytes-in loop ① kernel ≡ decode → Modulus → ``vocab.update``,
    including a nonzero global row offset (the sharded / absorb seeding)."""
    n_fields = 1 + n_dense + n_sparse

    def fresh():
        st0 = vocab_lib.VocabState.init(n_sparse, vocab_range)
        return dataclasses.replace(st0, rows_seen=jnp.int32(offset))

    got = fdv_ops.fused_decode_update(
        fresh(),
        jnp.asarray(buf),
        n_fields=n_fields,
        hex_start=1 + n_dense,
        max_rows=max_rows,
        block=BLOCK,
    )
    want = core_ops.fused_decode_vocab_update(
        fresh(),
        jnp.asarray(buf),
        n_fields=n_fields,
        n_dense=n_dense,
        n_sparse=n_sparse,
        max_rows=max_rows,
        use_kernel=False,
    )
    np.testing.assert_array_equal(
        np.asarray(got.first_pos), np.asarray(want.first_pos)
    )
    assert int(got.rows_seen) == int(want.rows_seen)


def _assert_xform_agree(buf, n_dense, n_sparse, max_rows, vocab_range, seed):
    """Bytes-in loop ② kernel ≡ decode → Modulus → gather → Neg2Zero+Log1p
    against a vocabulary built from the same hostile chunk."""
    n_fields = 1 + n_dense + n_sparse
    state = core_ops.fused_decode_vocab_update(
        vocab_lib.VocabState.init(n_sparse, vocab_range),
        jnp.asarray(buf),
        n_fields=n_fields,
        n_dense=n_dense,
        n_sparse=n_sparse,
        max_rows=max_rows,
        use_kernel=False,
    )
    vocab = vocab_lib.finalize(state)
    got = fdx_ops.fused_decode_transform(
        vocab,
        jnp.asarray(buf),
        n_fields=n_fields,
        hex_start=1 + n_dense,
        max_rows=max_rows,
        block=BLOCK,
    )
    want = core_ops.fused_decode_transform(
        vocab,
        jnp.asarray(buf),
        n_fields=n_fields,
        n_dense=n_dense,
        n_sparse=n_sparse,
        max_rows=max_rows,
        use_kernel=False,
    )
    for name, g, w in zip(("label", "dense", "ids", "valid"), got, want):
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(w), err_msg=f"xform {name}"
        )


def _assert_all_paths(buf, n_dense, n_sparse, max_rows, vocab_range, offset):
    _assert_decode_agree(buf, n_dense, n_sparse, max_rows)
    if n_sparse:
        _assert_vocab_agree(
            buf, n_dense, n_sparse, max_rows, vocab_range, offset
        )
        if n_dense:
            _assert_xform_agree(
                buf, n_dense, n_sparse, max_rows, vocab_range, offset
            )


# --------------------------------------------------------------------- #
# hypothesis properties (skip without the optional dep)
# --------------------------------------------------------------------- #


@settings(max_examples=30, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    n_dense=st.integers(0, 4),
    n_sparse=st.integers(0, 4),
    n_rows=st.integers(0, 24),
    truncate=st.integers(0, 40),
)
def test_fuzz_hostile_chunks(seed, n_dense, n_sparse, n_rows, truncate):
    """Property: every decode path agrees with the reference scan on
    arbitrary hostile chunks (all classes, random truncation)."""
    if n_dense + n_sparse == 0:
        n_sparse = 1
    buf = _hostile_chunk(seed, n_dense, n_sparse, n_rows, truncate)
    _assert_all_paths(buf, n_dense, n_sparse, 32, 53, seed % 1000)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), n_rows=st.integers(33, 48))
def test_fuzz_row_overflow(seed, n_rows):
    """Property: chunks with more rows than ``max_rows`` drop overflow
    rows identically on every path (the ``n_cap`` guard)."""
    buf = _hostile_chunk(seed, 2, 3, n_rows, 0)
    _assert_all_paths(buf, 2, 3, 32, 53, 0)


# --------------------------------------------------------------------- #
# deterministic corpus — the same classes, always on
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("seed", range(8))
def test_corpus_hostile_chunks(seed):
    """Seeded sweep over the hostile-row classes, mixed per chunk."""
    rng = np.random.default_rng(seed)
    n_dense, n_sparse = int(rng.integers(1, 5)), int(rng.integers(1, 5))
    buf = _hostile_chunk(
        seed, n_dense, n_sparse, int(rng.integers(1, 30)), int(rng.integers(0, 30))
    )
    _assert_all_paths(buf, n_dense, n_sparse, 32, 53, seed * 7)


@pytest.mark.parametrize(
    "raw",
    [
        b"",  # all-padding chunk
        b"\n\n\n",  # bare newlines (three all-empty rows)
        b"\t\t\t\t\t\n",  # one all-delimiter row
        b"1\t2\t3\tab\tcd\n9\t8\t7\tee",  # truncated mid-final-field
        b"1\t2\t3\tab\tcd",  # truncated with no delimiter at the cut
        b"1\t2\t3\tab\tcd\n9\t8\t7\t",  # truncated right after a delimiter
        b"1\t-2\t3\tdeadbeefdeadbeef\tgz!\n",  # overlong + invalid hex
        b"1\t2-3\t--4\tab\tcd\r\n",  # interior/double minus + CRLF
        b"0\t" + b"9" * 300 + b"\t3\tab\tcd\n",  # field straddles tiles
    ],
    ids=[
        "padding_only",
        "bare_newlines",
        "all_delim",
        "trunc_mid_field",
        "trunc_no_delim",
        "trunc_at_delim",
        "overlong_invalid_hex",
        "weird_minus_crlf",
        "tile_straddle",
    ],
)
def test_corpus_handcrafted(raw):
    """Handcrafted hostile chunks, one per adversarial class."""
    buf = synth.pad_bytes(raw, multiple=BLOCK)
    _assert_all_paths(buf, 2, 2, 8, 17, 3)


def test_corpus_truncation_sweep():
    """Every cut position of a two-row chunk (each byte of the final row
    in turn, including the newline) agrees on every path."""
    rows = b"1\t-7\t0\tdeadbeef\tcafe\n0\t12\t\tf00d\tbeef\n"
    for cut in range(1, 20):
        buf = synth.pad_bytes(rows[:-cut], multiple=BLOCK)
        _assert_all_paths(buf, 2, 2, 8, 17, 0)


# --------------------------------------------------------------------- #
# engine paths — fused decode vs unfused engine on hostile chunks
# --------------------------------------------------------------------- #


def _engine(use_fd: bool, schema) -> pipeline_lib.PiperPipeline:
    return pipeline_lib.PiperPipeline(
        pipeline_lib.PipelineConfig(
            schema=schema,
            max_rows_per_chunk=32,
            use_fused_decode=use_fd,
            use_fused_kernel=use_fd,
            use_fused_vocab=use_fd,
        )
    )


def test_engine_fused_decode_on_hostile_stream():
    """PiperPipeline with ``use_fused_decode`` on vs off: identical
    vocabulary and identical transforms over a hostile chunk stream
    (the last chunk's final row truncated)."""
    from repro.core import schema as schema_lib

    schema = schema_lib.TableSchema(n_dense=3, n_sparse=4, vocab_range=101)
    chunks = [
        _hostile_chunk(seed, 3, 4, 12, truncate=(11 if seed == 4 else 0))
        for seed in range(5)
    ]
    outs = {}
    for use_fd in (False, True):
        pipe = _engine(use_fd, schema)
        assert pipe._bytes_vocab == use_fd and pipe._bytes_xform == use_fd
        vocab = pipe.build_vocab_stream(iter(chunks))
        outs[use_fd] = (vocab, list(pipe.transform_stream(vocab, iter(chunks))))
    v0, o0 = outs[False]
    v1, o1 = outs[True]
    np.testing.assert_array_equal(np.asarray(v0.table), np.asarray(v1.table))
    np.testing.assert_array_equal(np.asarray(v0.sizes), np.asarray(v1.sizes))
    for a, b in zip(o0, o1):
        for name in ("label", "dense", "sparse", "valid"):
            np.testing.assert_array_equal(
                np.asarray(getattr(a, name)),
                np.asarray(getattr(b, name)),
                err_msg=name,
            )


def test_engine_hbm_tier_falls_back():
    """A vocab range beyond the VMEM budget routes the bytes-in dispatch
    to the decode + decoded-chain fallback — same results, and the
    compiled plan reports the tier."""
    from repro.core import schema as schema_lib

    schema = schema_lib.TableSchema(n_dense=2, n_sparse=2, vocab_range=1_000_000)
    buf = _hostile_chunk(9, 2, 2, 10, 0)
    pipe_f, pipe_u = _engine(True, schema), _engine(False, schema)
    assert pipe_f.compiled.decode_vocab_route == "bytes/hbm_slab"
    assert pipe_f.compiled.decode_xform_route(32) == "bytes/hbm"
    v_f = pipe_f.build_vocab_stream([buf])
    v_u = pipe_u.build_vocab_stream([buf])
    np.testing.assert_array_equal(np.asarray(v_f.table), np.asarray(v_u.table))
    a = pipe_f.transform_chunk(v_f, jnp.asarray(buf))
    b = pipe_u.transform_chunk(v_u, jnp.asarray(buf))
    for name in ("label", "dense", "sparse", "valid"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, name)), np.asarray(getattr(b, name))
        )


def test_stream_service_hostile_payloads():
    """The online service with fused decode serves hostile (whole-row)
    payloads identically to the unfused service: same absorbed vocab
    state, same per-request features."""
    import time

    from repro.core import schema as schema_lib
    from repro.stream import StreamingPreprocessService

    schema = schema_lib.TableSchema(n_dense=2, n_sparse=3, vocab_range=97)
    rng = np.random.default_rng(5)
    mk = lambda seed, n: _hostile_chunk(seed, 2, 3, n, 0)
    seed_chunk = mk(0, 20)
    absorb_payload = np.frombuffer(
        b"".join(
            _hostile_row(rng, k, 2, 3) + b"\n"
            for k in ("empty_fields", "invalid_hex", "overlong_hex", "all_delim")
        ),
        np.uint8,
    )
    requests = [mk(s, 6) for s in (2, 3)]

    def run(use_fd):
        pc = pipeline_lib.PipelineConfig(
            schema=schema,
            max_rows_per_chunk=32,
            use_fused_decode=use_fd,
            use_fused_kernel=use_fd,
            use_fused_vocab=use_fd,
        )
        state = pipeline_lib.PiperPipeline(pc).build_state_stream([seed_chunk])
        svc = StreamingPreprocessService(pc, state, bucket_rows=(32,), queue_depth=4)
        with svc:
            svc.absorb(absorb_payload, row_offset=20)
            deadline = time.time() + 30
            while int(np.asarray(svc.vocab_state.rows_seen)) < 24:
                assert time.time() < deadline, "absorb never landed"
                time.sleep(0.005)
            handles = [svc.submit(r[: np.flatnonzero(r == 10)[-1] + 1]) for r in requests]
            svc.drain(timeout=60)
            res = [h.result(timeout=30) for h in handles]
        return np.asarray(svc.vocab_state.first_pos), res

    st0, r0 = run(False)
    st1, r1 = run(True)
    np.testing.assert_array_equal(st0, st1)
    for a, b in zip(r0, r1):
        for k in a:
            np.testing.assert_array_equal(
                np.asarray(a[k]), np.asarray(b[k]), err_msg=k
            )
