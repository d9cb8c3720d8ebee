"""Data-parallel (row-sharded) two-loop engine: Piper's multi-instance mode.

The paper's scaling argument (§2, Fig. 8) is that row-partitioned CPU
preprocessing collapses because every thread/server must synchronize on
the shared vocabulary; Piper instead gives each instance *local* GenVocab
state and merges the states once, cheaply, at the end. This module is
that deployment shape on a JAX device mesh:

  * the dataset is row-sharded over a 1-D ``('data',)`` mesh axis
    (``launch.mesh.make_data_mesh``) — each device is one Piper instance;
  * **loop ①** runs under ``shard_map``: every shard scans its own chunk
    stack and accumulates a private :class:`vocab.VocabState`, with row
    positions taken from the feed's *global* offsets so the appearing
    order is well-defined across shards without any communication;
  * the per-shard states are reduced with the commutative-monoid
    ``vocab.merge`` in a log-depth tree (``vocab.merge_tree``) — the one
    and only synchronization point of the epoch;
  * **loop ②** is embarrassingly parallel: the finalized vocabulary is
    replicated (read-only) and every shard transforms its own rows; the
    output stays row-sharded exactly how a data-parallel trainer wants it.

Relation to ``core.sharded.ShardedPiper``: that engine is *column*-
parallel (vocab state split over a ``model`` axis, the FPGA layout); this
one is *row*-parallel (state replicated per shard, merged once — the
multi-server layout). The two compose: a 2-D ``('data','model')`` mesh
gives column-parallel instances inside row-parallel replicas.

Determinism contract: for the same chunk sequence,
``ShardedPiperPipeline.run_scan`` is **bit-identical** to
``PiperPipeline.run_scan`` — same vocabulary ordinals, same dense
transforms — for any shard count (tests/test_sharded_pipeline.py).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro import obs
from repro.core import pipeline as pipeline_lib
from repro.core import schema as schema_lib
from repro.core import vocab as vocab_lib
from repro.distributed import sharding as sharding_lib
from repro.launch.mesh import data_axes


class ShardedPiperPipeline:
    """Row-sharded two-loop preprocessing engine over a ``('data',)`` mesh.

    Args:
      config: the same :class:`~repro.core.pipeline.PipelineConfig` the
        single-device engine takes (schema, chunk geometry, input format,
        kernel routing, **plan** — all honored unchanged; the per-shard
        work is delegated to an inner
        :class:`~repro.core.pipeline.PiperPipeline`, so every shard
        executes the same compiled
        :class:`~repro.core.plan_compiler.CompiledPlan`: loop ① is the
        plan's vocab-building half — crossed features accumulate their
        own vocab rows — and loop ② its frozen-transform half, both
        inside the ``shard_map`` bodies). In particular the
        ``use_fused_kernel`` compiler hint applies per shard: each
        shard's canonical loop-② groups run the fused single-pass Pallas
        chain (kernels/fused_xform) inside its ``shard_map`` body, so the
        data-parallel deployment keeps the on-chip dataflow too. The
        same holds for loop ①'s ``use_fused_vocab`` hint: each shard
        accumulates its private ``VocabState`` through the fused
        Modulus → scatter-min dispatch (kernels/fused_vocab) inside
        ``shard_map``, and the monoid ``vocab.merge_tree`` reduction is
        unchanged — fused and unfused shards produce bit-identical
        states, so they merge interchangeably. And ``use_fused_decode``
        (utf8 feeds): the inner engine's bytes-in routing fires inside
        the ``shard_map`` bodies too, so each shard runs raw chunk bytes
        → vocab delta (loop ①) / → features (loop ②) as one dispatch —
        the decoded field table never materializes on any shard, and the
        merge tree still sees bit-identical states.
      mesh: a mesh whose row axes (``'data'``, optionally ``'pod'``) carry
        the shard dimension. Axes other than the row axes are ignored —
        chunks and state are not partitioned over them.

    The feed contract is ``TabularChunkFeed.shard_stacks()``:
    ``chunks [n_shards, n_steps, chunk_bytes]`` (or a pytree of binary
    arrays with the same two leading axes) plus global row
    ``offsets [n_shards, n_steps]``. Place them with
    ``distributed.sharding.put_shard_feed`` so no cross-device copy
    happens at dispatch.
    """

    def __init__(self, config: pipeline_lib.PipelineConfig, mesh: Mesh):
        self.config = config
        self.schema = config.schema
        self.mesh = mesh
        self.row_axes = data_axes(mesh)
        if not self.row_axes:
            raise ValueError(
                f"mesh {mesh.axis_names} has no 'data'/'pod' axis to shard rows over"
            )
        self.n_shards = 1
        for a in self.row_axes:
            self.n_shards *= mesh.shape[a]
        self._pipe = pipeline_lib.PiperPipeline(config)
        # the one program every shard executes (validated/grouped/routed
        # once; shard_map replicates the closure, not the compilation)
        self.plan = self._pipe.plan
        self.compiled = self._pipe.compiled
        # jitted entry points cached on the instance (same contract as
        # PiperPipeline: re-jitting per epoch would retrace)
        self._jit_shard_states = jax.jit(self._shard_states)
        self._jit_transform = jax.jit(self._sharded_transform)

    # -------------------------------------------------------------- #
    # spec helpers (leading axis = shard, rest local)
    # -------------------------------------------------------------- #
    def _feed_specs(self, chunks):
        return jax.tree.map(
            lambda x: P(self.row_axes, *([None] * (x.ndim - 1))), chunks
        )

    def _check_feed(self, chunks):
        # The shard_map bodies take block [0] — a mismatched shard axis
        # would silently drop every other stack, not error.
        lead = jax.tree.leaves(chunks)[0].shape[0]
        if lead != self.n_shards:
            raise ValueError(
                f"feed has {lead} shard stacks but the mesh has "
                f"{self.n_shards} row shards; build TabularChunkFeed with "
                f"n_row_shards={self.n_shards}"
            )

    # -------------------------------------------------------------- #
    # loop ① — per-shard local GenVocab, then monoid merge
    # -------------------------------------------------------------- #
    def _shard_states(self, chunks, offsets) -> vocab_lib.VocabState:
        """shard_map loop ①: one local VocabState per shard, stacked.

        Each shard scans its private chunk stack. The scan carry is the
        shard-local ``first_pos`` plus the shard's valid-row count; the
        *global* appearing order comes from seeding every chunk step's
        ``rows_seen`` with the feed's global row offset, so no shard ever
        needs to know how many rows the others have consumed.
        """

        track_counts = self.compiled.track_counts

        def local(chunks_blk, offsets_blk):
            chunks_local = jax.tree.map(lambda x: x[0], chunks_blk)
            offs = offsets_blk[0]

            # device-profile label: each shard's private loop-① scan shows
            # up named on the XLA timeline next to the host spans
            @jax.named_scope("piper.shard_loop1")
            def body(carry, xs):
                first_pos, counts, n_valid = carry
                chunk, off = xs
                st = dataclasses.replace(
                    init, first_pos=first_pos, rows_seen=off, counts=counts
                )
                st = self._pipe.vocab_step(st, chunk)
                # vocab_step advances rows_seen by the chunk's valid rows
                return (st.first_pos, st.counts, n_valid + st.rows_seen - off), None

            init = self._pipe.init_state()
            (first_pos, counts, n_valid), _ = jax.lax.scan(
                body,
                (init.first_pos, init.counts, init.rows_seen),
                (chunks_local, offs),
            )
            state = dataclasses.replace(
                init, first_pos=first_pos, rows_seen=n_valid, counts=counts
            )
            return jax.tree.map(lambda x: x[None], state)

        return shard_map(
            local,
            mesh=self.mesh,
            in_specs=(
                self._feed_specs(chunks),
                P(self.row_axes, None),
            ),
            out_specs=vocab_lib.VocabState(
                first_pos=P(self.row_axes, None),
                rows_seen=P(self.row_axes),
                counts=P(self.row_axes, None) if track_counts else None,
                layout=self.compiled.vocab_layout,
            ),
            check_vma=False,
        )(chunks, offsets)

    def build_state_scan(self, chunks, offsets) -> vocab_lib.VocabState:
        """Loop ① up to (but not including) finalization: per-shard local
        accumulation under ``shard_map``, then the monoid merge tree.

        The merged, un-finalized :class:`~repro.core.vocab.VocabState` is
        what the online streaming service consumes — it stays mergeable,
        so later deltas (new shards, new days of logs) fold in with
        ``vocab.merge`` and the service re-finalizes between steps.
        """
        self._check_feed(chunks)
        with obs.span(
            "loop1/shards",
            engine="sharded",
            shards=self.n_shards,
            route=self.compiled.vocab_route,
            tier=self.compiled.vocab_tier,
            slabs=self.compiled.vocab_slabs,
        ):
            states = self._jit_shard_states(chunks, offsets)
        # the epoch's one synchronization point: log-depth monoid reduce
        with obs.span("vocab/merge_tree", engine="sharded", shards=self.n_shards):
            return vocab_lib.merge_tree(states)

    def build_vocab_scan(self, chunks, offsets) -> vocab_lib.Vocabulary:
        """Loop ① end-to-end: local accumulation → merge tree → finalize.

        Args:
          chunks:  uint8 ``[n_shards, n_steps, chunk_bytes]`` (or binary
            pytree with the same leading axes), shard axis over the mesh.
          offsets: int32 ``[n_shards, n_steps]`` global first-row index of
            every chunk (``TabularChunkFeed.shard_stacks`` provides both).

        Returns:
          The finalized :class:`~repro.core.vocab.Vocabulary`, identical
          to what the single-device engine builds from the same chunk
          sequence.
        """
        state = self.build_state_scan(chunks, offsets)
        with obs.span("vocab/finalize"):
            return vocab_lib.finalize(state)

    # -------------------------------------------------------------- #
    # loop ② — embarrassingly parallel ApplyVocab + dense transforms
    # -------------------------------------------------------------- #
    def _sharded_transform(
        self, vocabulary: vocab_lib.Vocabulary, chunks
    ) -> schema_lib.ProcessedBatch:
        def local(vocab_rep, chunks_blk):
            chunks_local = jax.tree.map(lambda x: x[0], chunks_blk)

            @jax.named_scope("piper.shard_loop2")
            def body(carry, chunk):
                del carry
                return (), self._pipe.transform_chunk(vocab_rep, chunk)

            _, out = jax.lax.scan(body, (), chunks_local)
            return jax.tree.map(lambda x: x[None], out)

        # label/valid: [n_shards, n_steps, rows]; dense/sparse: [..., cols]
        row3 = P(self.row_axes, None, None)
        row4 = P(self.row_axes, None, None, None)
        return shard_map(
            local,
            mesh=self.mesh,
            in_specs=(
                # replicated
                vocab_lib.Vocabulary(table=P(), sizes=P(), layout=vocabulary.layout),
                self._feed_specs(chunks),
            ),
            out_specs=schema_lib.ProcessedBatch(
                label=row3, dense=row4, sparse=row4, valid=row3
            ),
            check_vma=False,
        )(vocabulary, chunks)

    def transform_scan(
        self, vocabulary: vocab_lib.Vocabulary, chunks
    ) -> schema_lib.ProcessedBatch:
        """Loop ② over the sharded feed with a replicated vocabulary.

        Collective-free: every shard gathers through its own copy of the
        read-only table. Output leaves keep the feed layout
        ``[n_shards, n_steps, rows, ...]`` with rows resident on their
        data shard; ``flatten_sharded`` recovers the single-device chunk
        order on host.
        """
        self._check_feed(chunks)
        # Replicate the read-only vocabulary up front: one explicit
        # broadcast instead of an implicit reshard on every jit call.
        vocabulary = jax.device_put(
            vocabulary, sharding_lib.replicated(self.mesh)
        )
        with obs.span(
            "loop2/shards",
            engine="sharded",
            shards=self.n_shards,
            route=self.compiled.xform_route,
            tier=self.compiled.tier,
        ):
            return self._jit_transform(vocabulary, chunks)

    # -------------------------------------------------------------- #
    # end-to-end
    # -------------------------------------------------------------- #
    def run_scan(self, chunks, offsets) -> schema_lib.ProcessedBatch:
        """Both loops over a device-resident sharded feed.

        Bit-identical to ``PiperPipeline.run_scan`` on the same chunk
        sequence (same ordinals, same dense floats), for any shard count.
        """
        vocabulary = self.build_vocab_scan(chunks, offsets)
        return self.transform_scan(vocabulary, chunks)


def flatten_sharded(out: schema_lib.ProcessedBatch) -> schema_lib.ProcessedBatch:
    """[n_shards, n_steps, rows, ...] → [n_shards*n_steps*rows, ...].

    Restores the round-robin chunk order of ``TabularChunkFeed`` (chunk i
    lives at shard ``i % n_shards``, step ``i // n_shards``), so the
    result row-matches ``pipeline.flatten_processed`` of the
    single-device engine on the same feed. Padding rows are kept;
    filter with ``out.valid``.
    """

    def flat(x):
        x = jnp.swapaxes(x, 0, 1)  # [n_steps, n_shards, rows, ...]
        return x.reshape((-1,) + x.shape[3:])

    return schema_lib.ProcessedBatch(
        label=flat(out.label),
        dense=flat(out.dense),
        sparse=flat(out.sparse),
        valid=flat(out.valid),
    )
