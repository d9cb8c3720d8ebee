"""Column-parallel sharded PIPER engine (the paper's core idea, on a mesh).

PIPER's claim: assign *columns* (not rows) to parallel workers and the
stateful vocabulary needs no synchronization, because each worker owns its
columns' state outright. On a TPU mesh we shard:

    rows            → ``data`` (× ``pod``) axes   (streaming chunks)
    sparse columns  → ``model`` axis              (per-column vocab state)

Each (data, model) shard decodes its row chunk (the byte stream is
replicated over ``model`` — the analogue of the FPGA decoder broadcasting
into per-column FIFOs: redundant decode compute is ~free next to the
stateful gather/scatter work) and updates only its local column tables.

The only collective in the whole preprocessing epoch is ONE elementwise
``min`` over the ``data``/``pod`` axes at vocabulary finalization —
replacing the CPU baseline's per-thread sub-dictionary merge (paper
Fig. 8's scaling collapse). Loop ② is collective-free: lookups hit the
local table shard, and outputs stay sharded exactly how the DLRM trainer
wants them (rows over ``data``, embedding-table columns over ``model``).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import ops
from repro.core import pipeline as pipeline_lib
from repro.core import schema as schema_lib
from repro.core import vocab as vocab_lib


def _row_axes(mesh: Mesh) -> tuple[str, ...]:
    """Mesh axes that shard rows: ('pod','data') if a pod axis exists."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _col_axis(mesh: Mesh) -> str:
    return "model"


def padded_cols(n_sparse: int, mesh: Mesh) -> int:
    m = mesh.shape[_col_axis(mesh)]
    return ((n_sparse + m - 1) // m) * m


@dataclasses.dataclass(eq=False)  # identity hash: instances are jit statics
class ShardedPiper:
    """Mesh-distributed two-loop engine.

    State layout: ``first_pos [n_row_shards, padded_cols, vocab_range]``
    sharded ``P(row_axes, 'model', None)`` — every (row-shard, column-shard)
    pair owns a private block; no write ever crosses a shard boundary.
    """

    config: pipeline_lib.PipelineConfig
    mesh: Mesh

    def __post_init__(self):
        self.schema = self.config.schema
        if not self.schema.uniform:
            raise ValueError(
                "ShardedPiper shards a rectangular [columns, range] state "
                "over the model axis and needs one range for every column; "
                f"schema {self.schema} has per-column ranges — use "
                "ShardedPiperPipeline (data-parallel) instead"
            )
        self.row_axes = _row_axes(self.mesh)
        self.n_row_shards = 1
        for a in self.row_axes:
            self.n_row_shards *= self.mesh.shape[a]
        self.model_size = self.mesh.shape[_col_axis(self.mesh)]
        self.cols_pad = padded_cols(self.schema.n_sparse, self.mesh)
        self.cols_local = self.cols_pad // self.model_size
        self._pipe = pipeline_lib.PiperPipeline(self.config)

    # -------------------------------------------------------------- #
    # state
    # -------------------------------------------------------------- #
    def state_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(self.row_axes, "model", None))

    def init_state(self) -> jnp.ndarray:
        return jax.device_put(
            jnp.full(
                (self.n_row_shards, self.cols_pad, self.schema.vocab_range),
                vocab_lib.NEVER,
                jnp.int32,
            ),
            self.state_sharding(),
        )

    # -------------------------------------------------------------- #
    # shared local stages
    # -------------------------------------------------------------- #
    def _decode_local(self, chunk_bytes: jnp.ndarray):
        """Decode a [1, chunk] local byte block → local batch (all columns)."""
        batch = self._pipe.decode_chunk(chunk_bytes[0])
        return batch

    def _local_col_slice(self, sparse_modded: jnp.ndarray) -> jnp.ndarray:
        """Select this model-shard's columns from the full decoded table."""
        # Pad columns so the split is even, then take the local block.
        pad = self.cols_pad - self.schema.n_sparse
        padded = jnp.pad(sparse_modded, ((0, 0), (0, pad)))
        k = jax.lax.axis_index(_col_axis(self.mesh))
        return jax.lax.dynamic_slice_in_dim(
            padded, k * self.cols_local, self.cols_local, axis=1
        )

    # -------------------------------------------------------------- #
    # loop ① — sharded GenVocab
    # -------------------------------------------------------------- #
    def vocab_step(self, state: jnp.ndarray, chunks: jnp.ndarray, offsets: jnp.ndarray):
        """One streaming step.

        chunks:  uint8 [n_row_shards, chunk_bytes] — one chunk per row shard
        offsets: int32 [n_row_shards] — global row offset of each chunk
                 (defines the global appearing order across shards)
        """

        def step(state_blk, chunk_blk, offset_blk):
            batch = self._decode_local(chunk_blk)
            modded = ops.positive_modulus(batch.sparse, self.schema.vocab_range)
            local = self._local_col_slice(modded)  # [rows, cols_local]
            rows = local.shape[0]
            pos = offset_blk[0] + jnp.arange(rows, dtype=jnp.int32)
            pos = jnp.where(batch.valid, pos, vocab_lib.NEVER)
            cols = jnp.arange(local.shape[1], dtype=jnp.int32)[None, :]
            upd = state_blk[0].at[
                jnp.broadcast_to(cols, local.shape), local
            ].min(jnp.broadcast_to(pos[:, None], local.shape))
            return upd[None]

        return shard_map(
            step,
            mesh=self.mesh,
            in_specs=(
                P(self.row_axes, "model", None),
                P(self.row_axes, None),
                P(self.row_axes),
            ),
            out_specs=P(self.row_axes, "model", None),
            check_vma=False,
        )(state, chunks, offsets)

    def finalize(self, state: jnp.ndarray) -> vocab_lib.Vocabulary:
        """THE one collective: min-reduce row shards, then rank locally."""

        @jax.jit
        def _fin(state):
            first_pos = jnp.min(state, axis=0)  # XLA: all-reduce(min) over rows
            first_pos = jax.lax.with_sharding_constraint(
                first_pos, NamedSharding(self.mesh, P("model", None))
            )
            return vocab_lib._rank_present(first_pos)

        table, sizes = _fin(state)
        return vocab_lib.Vocabulary(table=table, sizes=sizes)

    # -------------------------------------------------------------- #
    # loop ② — sharded ApplyVocab + dense transforms
    # -------------------------------------------------------------- #
    def transform_step(self, vocabulary: vocab_lib.Vocabulary, chunks: jnp.ndarray):
        """Transform one chunk set; outputs stay (rows@data, cols@model)."""

        def step(table_blk, chunk_blk):
            batch = self._decode_local(chunk_blk)
            modded = ops.positive_modulus(batch.sparse, self.schema.vocab_range)
            local = self._local_col_slice(modded)
            cols = jnp.arange(local.shape[1], dtype=jnp.int32)[None, :]
            ids = table_blk[jnp.broadcast_to(cols, local.shape), local]
            dense = ops.dense_transform(batch.dense)
            return (
                batch.label[None],
                dense[None],
                ids[None],
                batch.valid[None],
            )

        label, dense, ids, valid = shard_map(
            step,
            mesh=self.mesh,
            in_specs=(P("model", None), P(self.row_axes, None)),
            out_specs=(
                P(self.row_axes, None),
                P(self.row_axes, None, None),
                P(self.row_axes, None, "model"),
                P(self.row_axes, None),
            ),
            check_vma=False,
        )(vocabulary.table, chunks)
        # Columns stay padded to a multiple of the model axis (padding columns
        # hold ordinal 0 everywhere); downstream embedding tables are padded
        # identically so the sharding stays even. Consumers slice on host.
        return schema_lib.ProcessedBatch(
            label=label, dense=dense, sparse=ids, valid=valid
        )

    # -------------------------------------------------------------- #
    # end-to-end scan (benchmark / dry-run entry)
    # -------------------------------------------------------------- #
    @functools.partial(jax.jit, static_argnums=0)
    def run_scan(self, stacked_chunks: jnp.ndarray, offsets: jnp.ndarray):
        """Both loops over device-resident chunks.

        stacked_chunks: uint8 [n_steps, n_row_shards, chunk_bytes]
        offsets:        int32 [n_steps, n_row_shards]
        """

        def loop1(state, xs):
            chunk, off = xs
            return self.vocab_step(state, chunk, off), None

        state, _ = jax.lax.scan(loop1, self.init_state(), (stacked_chunks, offsets))
        vocabulary = self.finalize(state)

        def loop2(carry, chunk):
            del carry
            return (), self.transform_step(vocabulary, chunk)

        _, out = jax.lax.scan(loop2, (), stacked_chunks)
        return out
