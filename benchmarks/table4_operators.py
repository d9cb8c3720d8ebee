"""Table 4 analogue: per-operator execution time for the whole dataset.

The paper times each PE on the FPGA (II × clock) against single-thread
CPU. Here: numpy serial operator vs the vectorized jnp operator vs the
Pallas kernel (interpret mode — *algorithm* check, not TPU wall time;
the projected TPU numbers derive from the roofline analysis).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_fn, time_host
from repro.core import baseline, ops, schema as schema_lib, vocab as vocab_lib
from repro.data import synth
from repro.kernels.decode_utf8 import ref as dref
from repro.kernels.dense_xform import kernel as dx_kernel
from repro.kernels.vocab import kernel as v_kernel

ROWS = 4_000


def main() -> None:
    schema = schema_lib.CRITEO
    cfg = synth.SynthConfig(schema=schema, rows=ROWS, seed=0)
    buf, table = synth.make_dataset(cfg)
    hex_t = jnp.asarray(schema.field_is_hex())
    jbuf = jnp.asarray(buf)

    # Decode (+FillMissing)
    sec = time_host(lambda: baseline.decode_rows_serial(buf, schema), iters=1)
    emit("table4/decode/cpu_serial", sec, f"rows_per_s={ROWS/sec:.0f}")
    dec = lambda: dref.decode_bytes(
        jbuf, hex_t, n_fields=schema.n_fields, max_rows=8192,
        n_dense=schema.n_dense, n_sparse=schema.n_sparse,
    )
    sec = time_fn(dec)
    emit("table4/decode/jnp_scan", sec, f"rows_per_s={ROWS/sec:.0f}")

    sparse = jnp.asarray(table["sparse"])
    dense = jnp.asarray(table["dense"])

    # Hex2Int folded into decode; Modulus
    sec = time_host(lambda: baseline.positive_modulus(table["sparse"], 5000))
    emit("table4/modulus/cpu", sec, "")
    sec = time_fn(lambda: ops.positive_modulus(sparse, 5000))
    emit("table4/modulus/jnp", sec, "")

    # GenVocab-1 (+ApplyVocab-1): first-occurrence table build
    modded_np = baseline.positive_modulus(table["sparse"], 5000)
    modded = jnp.asarray(modded_np)
    sec = time_host(lambda: baseline.generate_vocab_thread(modded_np, schema), iters=1)
    emit("table4/genvocab/cpu_dict", sec, "")
    state = vocab_lib.VocabState.init(schema.n_sparse, 5000)
    sec = time_fn(
        lambda: vocab_lib.update(state, modded, jnp.ones(ROWS, bool)).first_pos
    )
    emit("table4/genvocab/jnp_scatter", sec, "")
    pos = jnp.arange(ROWS, dtype=jnp.int32)
    sec = time_fn(
        lambda: v_kernel.genvocab(
            jnp.full((schema.n_sparse, 5000), vocab_lib.NEVER, jnp.int32),
            modded.T, pos,
        )
    )
    emit("table4/genvocab/pallas_interpret", sec, "II=2 RMW loop (alg check)")

    # ApplyVocab-2: table lookup
    vocab = vocab_lib.finalize(
        vocab_lib.update(state, modded, jnp.ones(ROWS, bool))
    )
    table_dicts = [
        {int(v): i for i, v in enumerate(np.argsort(np.asarray(vocab.column(c)))[: int(vocab.sizes[c])])}
        for c in range(schema.n_sparse)
    ]
    sec = time_fn(lambda: vocab_lib.lookup(vocab, modded))
    emit("table4/applyvocab/jnp_gather", sec, "HBM tier")
    rect = vocab.layout.rect(vocab.table)
    sec = time_fn(lambda: v_kernel.apply_vocab(rect, modded.T, row_block=1000))
    emit("table4/applyvocab/pallas_interpret", sec, "VMEM tier (alg check)")

    # Neg2Zero + Logarithm
    sec = time_host(lambda: np.log1p(np.maximum(table["dense"], 0)).astype(np.float32))
    emit("table4/dense_xform/numpy", sec, "")
    sec = time_fn(lambda: ops.dense_transform(dense))
    emit("table4/dense_xform/jnp_fused", sec, "")
    sec = time_fn(lambda: dx_kernel.dense_transform(dense))
    emit("table4/dense_xform/pallas_interpret", sec, "")


if __name__ == "__main__":
    main()
