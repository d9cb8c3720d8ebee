"""Quickstart: the paper's pipeline in ~40 lines.

Raw UTF-8 Criteo-format rows → PIPER two-loop preprocessing
(Decode → Modulus → GenVocab → ApplyVocab ∥ Neg2Zero → Logarithm) →
vocabulary-encoded features, verified against the row-wise CPU oracle.

    PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.core import baseline, pipeline as P
from repro.data import synth
from repro.launch import compile_cache

compile_cache.enable()

# 1. synthesize a Criteo-format dataset (1 label + 13 dense + 26 sparse)
cfg = synth.SynthConfig(rows=2_000, seed=0)
buf, _ = synth.make_dataset(cfg)
print(f"dataset: {cfg.rows} rows, {buf.size/1e6:.2f} MB UTF-8")

# 2. the PIPER engine: loop ① builds the vocabulary, loop ② applies it —
#    streaming over row-framed chunks, state carried between chunks
pipe = P.PiperPipeline(
    P.PipelineConfig(schema=cfg.schema, chunk_bytes=1 << 16, max_rows_per_chunk=1024)
)
chunks = lambda: synth.chunk_stream(buf, 1 << 16)

vocab = pipe.build_vocab_stream(chunks())
print(f"loop ① done: vocab sizes per column, e.g. {np.asarray(vocab.sizes[:6])}")

rows = 0
outs = []
for out in pipe.transform_stream(vocab, chunks()):
    v = np.asarray(out.valid)
    outs.append((np.asarray(out.sparse)[v], np.asarray(out.dense)[v]))
    rows += int(v.sum())
print(f"loop ② done: {rows} rows transformed")

# 3. verify bit-exact against the paper's row-wise CPU pipeline
oracle = baseline.run_pipeline(buf, cfg.schema, n_threads=4)
sparse = np.concatenate([s for s, _ in outs])
dense = np.concatenate([d for _, d in outs])
np.testing.assert_array_equal(sparse, oracle["sparse"])
np.testing.assert_allclose(dense, oracle["dense"], rtol=1e-6)
print("verified: columnar engine == row-wise CPU oracle (bit-exact ordinals)")
print("sample row 0 sparse ordinals:", sparse[0][:8], "dense:", dense[0][:4])
