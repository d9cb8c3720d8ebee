"""Network-attached streaming preprocessing (paper §3.4.2).

Simulates the disaggregated deployment: the dataset is produced in
row-framed packets by a generator ("the network"), never materialized in
full; the engine streams both loops with only the per-column vocabulary
state held between chunks — datasets larger than (device) memory.

    PYTHONPATH=src python examples/preprocess_stream.py [--mb 64]
"""

import argparse
import time

import numpy as np

from repro.core import pipeline as P, schema as schema_lib
from repro.data import synth
from repro.launch import compile_cache


def packet_stream(total_rows: int, rows_per_packet: int, chunk_bytes: int, seed=0):
    """Generator of row-framed byte packets (fresh each epoch/loop)."""
    done = 0
    shard = 0
    while done < total_rows:
        n = min(rows_per_packet, total_rows - done)
        cfg = synth.SynthConfig(rows=n, seed=(seed, shard).__hash__() & 0x7FFFFFFF)
        buf, _ = synth.make_dataset(cfg)
        yield from synth.chunk_stream(buf, chunk_bytes)
        done += n
        shard += 1


def main() -> None:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=30_000)
    ap.add_argument("--chunk-kb", type=int, default=256)
    args = ap.parse_args()

    schema = schema_lib.CRITEO
    chunk_bytes = args.chunk_kb << 10
    pipe = P.PiperPipeline(
        P.PipelineConfig(schema=schema, chunk_bytes=chunk_bytes, max_rows_per_chunk=4096)
    )
    stream = lambda: packet_stream(args.rows, 5_000, chunk_bytes)

    t0 = time.perf_counter()
    vocab = pipe.build_vocab_stream(stream())
    t1 = time.perf_counter()
    rows = bytes_seen = 0
    for out in pipe.transform_stream(vocab, stream()):
        rows += int(np.asarray(out.valid).sum())
        bytes_seen += chunk_bytes
    t2 = time.perf_counter()

    print(f"loop ① (GenVocab): {t1-t0:.2f}s — vocab sizes {np.asarray(vocab.sizes[:5])}...")
    print(
        f"loop ② (ApplyVocab): {t2-t1:.2f}s — {rows} rows, "
        f"{bytes_seen/1e6:.1f} MB streamed, state footprint = "
        f"{vocab.table.size*4/1e6:.1f} MB (constant, independent of dataset size)"
    )
    print(f"throughput: {rows/(t2-t0):.0f} rows/s end-to-end on host CPU")


if __name__ == "__main__":
    main()
