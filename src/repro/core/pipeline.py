"""The PIPER two-loop preprocessing pipeline (paper Figure 5).

Loop ① streams the dataset once and accumulates the per-column vocabulary
state; loop ② re-streams it and emits the final table. Between chunks the
only carried state is :class:`vocab.VocabState` — so the engine processes
datasets far larger than device memory, exactly like the network-attached
PIPER ("the FPGA is capable of processing datasets larger than its memory
capacity in a streaming fashion").

Two execution styles:
  * ``*_stream``  — host-driven: a Python iterator of byte chunks feeds a
    jitted chunk-step (the realistic out-of-core / network path; chunks
    can come from disk, a socket, or the data loader's prefetch queue).
  * ``*_scan``    — device-driven: all chunks stacked in one array, looped
    with ``lax.scan`` (fully jitted; used for benchmarks and the dry-run).

The per-chunk operator chain is **plan-driven**: ``PipelineConfig.plan``
holds a declarative :class:`~repro.core.plan.PreprocPlan` (default:
``plan.criteo_default`` — exactly Figure 5's
    LoadData → Decode(+FillMissing) → [sparse: Modulus → GenVocab →
    ApplyVocab] ∥ [dense: Neg2Zero → Logarithm] → StoreData
) which ``plan_compiler.compile_plan`` validates, groups by op-chain
signature, and tier-routes into one :class:`~repro.core.plan_compiler.
CompiledPlan`. The engine only ever executes the compiled plan's two
halves — ``vocab_step`` (loop ①) and ``transform`` (loop ②) — so
arbitrary per-column recipes (crossed features, bucketized dense,
non-Criteo schemas) run through the same machinery.

Loop ②'s canonical groups can run as ONE fused Pallas dispatch
(``PipelineConfig.use_fused_kernel`` — a compiler hint, resolved by
``kernels.resolve_fused``; kernels/fused_xform): the row tile streams
through Modulus → ApplyVocab ∥ Neg2Zero → Logarithm entirely on-chip,
the paper's no-intermediate-materialization dataflow. Loop ① gets the
matching treatment (``PipelineConfig.use_fused_vocab``;
kernels/fused_vocab): the row tile's uint32 Modulus and the GenVocab
scatter-min into the VMEM-resident ``VocabState`` fuse into one
dispatch, completing the "both loops single-pass" story. For utf8
feeds, ``PipelineConfig.use_fused_decode`` pushes the fusion one stage
earlier: Decode itself joins both kernels
(kernels/fused_decode_vocab, kernels/fused_decode_xform), so each loop
goes raw bytes → features in ONE dispatch and the decoded field table
never materializes in HBM. Defaults (None) auto-enable all three
wherever Pallas compiles (TPU backend); the unfused per-op chains
remain the differential oracles (knob False).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Iterable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import plan as plan_lib
from repro.core import plan_compiler
from repro.core import schema as schema_lib
from repro.core import vocab as vocab_lib


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    schema: schema_lib.TableSchema = schema_lib.CRITEO
    chunk_bytes: int = 1 << 20
    # Static per-chunk row capacity. Criteo rows are ≥ ~80 B encoded, but we
    # keep headroom; unclaimed rows carry valid=False.
    max_rows_per_chunk: int = 1 << 14
    # Input already decoded ("binary", the paper's Config III) or raw UTF-8.
    input_format: str = "utf8"
    # Route hot ops through the per-op Pallas kernels (interpreted on
    # the CPU). Opt-in: on a TPU Mosaic refuses the vocab and decode
    # kernels' (1, block) tiles (ROADMAP speed item 2).
    use_kernels: bool = False
    # COMPILER HINT — canonical loop-② groups (Modulus → ApplyVocab ∥
    # Neg2Zero → Logarithm) as one fused Pallas dispatch instead of
    # per-op calls with HBM round-trips between them (kernels/fused_xform).
    # None = auto via `kernels.resolve_fused()`: on when Pallas is
    # available *compiled* — i.e. the toolchain imports and the default
    # backend is TPU. On CPU Pallas only interprets (slower than the
    # XLA-fused unfused chain), so auto resolves off there and the fused
    # path is opt-in via True — the same reason `use_kernels` defaults
    # False. Outputs are bit-identical on sparse ids and allclose (same
    # f32 formula) on dense vs. the unfused chain either way. On a TPU
    # the VMEM tier's kernel does not lower, so the plan compiler keeps
    # that tier unfused there (plan_compiler.CompiledPlan).
    use_fused_kernel: bool | None = None
    # COMPILER HINT — loop ①'s canonical vocab group (uint32 Modulus →
    # GenVocab scatter-min over every vocab column, crosses included) as
    # one fused Pallas dispatch with the VocabState resident in VMEM
    # across row tiles (kernels/fused_vocab), instead of separate
    # modulus and scatter dispatches with an HBM round-trip between
    # them. Same auto semantics as `use_fused_kernel`: None resolves
    # via `kernels.resolve_fused()` (on iff Pallas *compiles*, i.e. TPU
    # backend; CPU interpret mode is slower than the XLA-fused unfused
    # chain, so auto stays off there and tests/CI opt in explicitly).
    # State is bit-identical to the unfused chain either way —
    # scatter-min is order-independent.
    use_fused_vocab: bool | None = None
    # COMPILER HINT — fuse Decode itself into both loop kernels for utf8
    # feeds: loop ① runs bytes → Modulus → GenVocab scatter-min and loop
    # ② runs bytes → Modulus → ApplyVocab ∥ Neg2Zero → Logarithm as ONE
    # Pallas dispatch each (kernels/fused_decode_vocab,
    # kernels/fused_decode_xform), so a UTF-8 chunk touches HBM once —
    # the decoded field table never materializes. Applies only when
    # `input_format == "utf8"` (binary feeds — the paper's Config III —
    # skip decode entirely) and only for plans that are the identity
    # over the wire layout (the compiler records admissibility as
    # `CompiledPlan.decode_*_dispatch`); per-chunk the wrappers still
    # tier-route against the shared 8 MiB VMEM residency budget and
    # fall back to decode + the decoded-input chains beyond it. Unlike
    # the other fused hints, None resolves to **off on every backend**:
    # Mosaic refuses both bytes-in kernels as written (their (1, block)
    # byte tiles break the TPU tiling; ROADMAP speed item 2). Opt in with
    # True (what the differential tests and CPU interpret-mode runs do).
    # Outputs are bit-identical on sparse ids/labels/state and
    # identical-formula on dense either way.
    use_fused_decode: bool | None = None
    # Carry the occurrence-count plane beside first_pos in the loop-①
    # state (VocabState.counts) — required by the frequency-capped
    # finalizers (vocab.finalize_topk / finalize_min_count). Doubles the
    # per-entry state footprint, so it tightens the VMEM residency
    # cutoff; counts merge by elementwise + (order-independent), keeping
    # every engine bit-deterministic under resharding. The bytes-in
    # loop-① kernel carries no count plane, so enabling this routes utf8
    # loop ① through decode + the decoded-input (slab-capable) chain.
    track_vocab_counts: bool = False
    # EXPERT/TEST KNOB — force loop ①'s hbm_slab tier with this
    # per-column slab width (128-lane multiples; None = tier policy
    # decides from the state footprint). Lets tests and benchmarks pin
    # slab/VMEM bit-identity on ranges that fit both tiers.
    vocab_slab_range: int | None = None
    # The declarative per-column preprocessing program (core/plan.py).
    # None = `plan.criteo_default(schema)` — the paper's exact chain, so
    # every pre-IR call site keeps its behavior bit-for-bit. Compiled once
    # per engine by `plan_compiler.compile_plan`.
    plan: plan_lib.PreprocPlan | None = None

    def __post_init__(self):
        if self.input_format not in ("utf8", "binary"):
            raise ValueError(f"unknown input_format: {self.input_format}")

    @property
    def fused_enabled(self) -> bool:
        """The resolved ``use_fused_kernel`` hint (None → on iff the
        Pallas toolchain imports and it compiles on this backend —
        ``kernels.resolve_fused``)."""
        if self.use_fused_kernel is None:
            from repro import kernels as kernels_lib

            return kernels_lib.resolve_fused()
        return self.use_fused_kernel

    @property
    def fused_vocab_enabled(self) -> bool:
        """The resolved ``use_fused_vocab`` hint (None → on iff the
        Pallas toolchain imports and it compiles on this backend —
        ``kernels.resolve_fused``)."""
        if self.use_fused_vocab is None:
            from repro import kernels as kernels_lib

            return kernels_lib.resolve_fused()
        return self.use_fused_vocab

    @property
    def fused_decode_enabled(self) -> bool:
        """The resolved ``use_fused_decode`` hint. None → **off**: Mosaic
        refuses the bytes-in kernels, so the path stays opt-in — see the
        field comment. Only consulted for utf8 feeds."""
        if self.use_fused_decode is None:
            return False
        return self.use_fused_decode

    def resolved_plan(self) -> plan_lib.PreprocPlan:
        """The plan this config executes (None → the Criteo default)."""
        return self.plan if self.plan is not None else plan_lib.criteo_default(self.schema)


class PiperPipeline:
    """Two-loop columnar preprocessing engine (executes a CompiledPlan)."""

    def __init__(self, config: PipelineConfig):
        self.config = config
        self.schema = config.schema
        self.plan = config.resolved_plan()
        # The plan is compiled once per engine; both loops below only ever
        # execute its two halves, so every path — single-device, each
        # shard of ShardedPiperPipeline, every streaming-service bucket —
        # runs the same validated, grouped, tier-routed program.
        self.compiled = plan_compiler.compile_plan(
            self.plan,
            self.schema,
            fused=config.fused_enabled,
            use_kernels=config.use_kernels,
            fused_vocab=config.fused_vocab_enabled,
            fused_decode=config.fused_decode_enabled,
            track_counts=config.track_vocab_counts,
            vocab_slab_range=config.vocab_slab_range,
        )
        # Bytes-in routing is static per engine: utf8 feed + an identity-
        # layout plan + the hint on. The per-chunk VMEM/HBM tier choice
        # stays inside the ops wrappers (it depends on max_rows).
        self._bytes_vocab = (
            config.input_format == "utf8" and self.compiled.decode_vocab_dispatch
        )
        self._bytes_xform = (
            config.input_format == "utf8" and self.compiled.decode_xform_dispatch
        )
        self._hex_table = jnp.asarray(self.schema.field_is_hex())
        # jitted chunk steps are cached on the instance: re-jitting per
        # stream pass would retrace/recompile on every epoch. Loop ①'s
        # step consumes its state (the fused kernels update it in place;
        # without the donation XLA copies the whole state every chunk).
        self._jit_vocab_step = jax.jit(self.vocab_step, donate_argnums=0)
        self._jit_transform_chunk = jax.jit(self.transform_chunk)
        # Span labels: the compiled plan's tier + route metadata, stamped
        # on every per-chunk span so the trace says *which* code path
        # (fused/vmem, fused/hbm, unfused, bytes/...) the time went to.
        self._vocab_span_labels = {
            "engine": "piper",
            "route": (
                self.compiled.decode_vocab_route
                if self._bytes_vocab
                else self.compiled.vocab_route
            ),
            "tier": self.compiled.vocab_tier,
            "slabs": self.compiled.vocab_slabs,
        }
        self._xform_span_labels = {
            "engine": "piper",
            "route": (
                self.compiled.decode_xform_route(config.max_rows_per_chunk)
                if self._bytes_xform
                else self.compiled.xform_route
            ),
            "tier": self.compiled.tier,
        }
        # Process-wide rows/bytes counters (per loop). utf8 rows are
        # counted from newline frames when the chunk is host-resident;
        # byte counts include the chunk padding the engine processed.
        m = obs.metrics()
        self._c_chunks = {
            "loop1": m.counter("pipeline.loop1_chunks_total"),
            "loop2": m.counter("pipeline.loop2_chunks_total"),
        }
        self._c_rows = {
            "loop1": m.counter("pipeline.loop1_rows_total"),
            "loop2": m.counter("pipeline.loop2_rows_total"),
        }
        self._c_bytes = {
            "loop1": m.counter("pipeline.loop1_bytes_total"),
            "loop2": m.counter("pipeline.loop2_bytes_total"),
        }

    def _note_chunk(self, loop: str, chunk) -> None:
        """Count one processed chunk (host-side, no device sync: jax
        arrays only contribute their static byte size)."""
        self._c_chunks[loop].add(1)
        if self.config.input_format == "utf8":
            self._c_bytes[loop].add(int(np.size(chunk)))
            if isinstance(chunk, np.ndarray):
                self._c_rows[loop].add(int((chunk == schema_lib.NEWLINE).sum()))
        else:
            self._c_rows[loop].add(int(chunk["label"].shape[0]))

    # ------------------------------------------------------------------ #
    # Decode stage
    # ------------------------------------------------------------------ #
    def decode_chunk(self, chunk: jnp.ndarray) -> schema_lib.TabularBatch:
        """Decode one padded UTF-8 chunk (whole rows) into a TabularBatch."""
        with jax.named_scope("piper.decode"):
            return self._decode_chunk(chunk)

    def _decode_chunk(self, chunk: jnp.ndarray) -> schema_lib.TabularBatch:
        if self.config.use_kernels:
            from repro.kernels.decode_utf8 import ops as decode_ops

            label, dense, sparse, valid = decode_ops.decode(
                chunk,
                self._hex_table,
                n_fields=self.schema.n_fields,
                max_rows=self.config.max_rows_per_chunk,
                n_dense=self.schema.n_dense,
                n_sparse=self.schema.n_sparse,
            )
        else:
            from repro.kernels.decode_utf8 import ref as decode_ref

            label, dense, sparse, valid = decode_ref.decode_bytes(
                chunk,
                self._hex_table,
                n_fields=self.schema.n_fields,
                max_rows=self.config.max_rows_per_chunk,
                n_dense=self.schema.n_dense,
                n_sparse=self.schema.n_sparse,
            )
        return schema_lib.TabularBatch(
            label=label, dense=dense, sparse=sparse, valid=valid
        )

    def _as_batch(self, chunk) -> schema_lib.TabularBatch:
        """Normalize an input chunk (utf8 bytes or binary dict) to a batch."""
        if self.config.input_format == "utf8":
            return self.decode_chunk(chunk)
        valid = chunk.get("valid")
        if valid is None:
            valid = jnp.ones(chunk["label"].shape[0], bool)
        return schema_lib.TabularBatch(
            label=chunk["label"],
            dense=chunk["dense"],
            sparse=chunk["sparse"],
            valid=valid,
        )

    # ------------------------------------------------------------------ #
    # Loop ① — GenVocab
    # ------------------------------------------------------------------ #
    def init_state(self) -> vocab_lib.VocabState:
        return self.compiled.init_state()

    def vocab_step(
        self, state: vocab_lib.VocabState, chunk
    ) -> vocab_lib.VocabState:
        with jax.named_scope("piper.loop1"):
            if self._bytes_vocab:
                # bytes-in loop ①: the raw chunk IS the kernel input — no
                # decoded field table ever materializes (tier-routed; the
                # wrapper falls back to decode + the decoded-input chain on
                # the HBM tier). Bit-identical to the branch below.
                return self.compiled.vocab_step_bytes(
                    state, chunk, max_rows=self.config.max_rows_per_chunk
                )
            return self.compiled.vocab_step(state, self._as_batch(chunk))

    def build_state_stream(self, chunks: Iterable) -> vocab_lib.VocabState:
        """Loop ① over a host iterator, stopping *before* finalization.

        The un-finalized :class:`vocab.VocabState` is the mergeable
        artifact: hand it to ``stream.StreamingPreprocessService`` so the
        online service can keep absorbing deltas (``vocab.merge``) and
        re-finalize between serving steps.
        """
        state = self.init_state()
        cap = self.config.max_rows_per_chunk
        # Host-side stream-length guard: positions are int32, so a stream
        # may carry at most vocab.MAX_ROWS rows (beyond that the kernels
        # saturate and silently drop rows). Track a no-sync upper bound
        # (rows_seen inside the jitted step is an unsynced device value);
        # only when the bound would cross the ceiling, sync the true
        # count and fail loudly if the next chunk could overflow.
        rows_ub = 0
        for chunk in chunks:
            rows_ub += cap
            if rows_ub > vocab_lib.MAX_ROWS:
                seen = int(state.rows_seen)
                if seen + cap > vocab_lib.MAX_ROWS:
                    raise OverflowError(
                        f"loop ① stream exceeds the int32 position ceiling: "
                        f"{seen} rows seen + up to {cap} more > "
                        f"{vocab_lib.MAX_ROWS}"
                    )
                rows_ub = seen + cap
            self._note_chunk("loop1", chunk)
            chunk = jax.tree.map(jnp.asarray, chunk)
            with obs.span("loop1/chunk", **self._vocab_span_labels):
                state = self._jit_vocab_step(state, chunk)
        return state

    def build_vocab_stream(self, chunks: Iterable) -> vocab_lib.Vocabulary:
        """Loop ① over a host iterator (out-of-core / network path)."""
        state = self.build_state_stream(chunks)
        with obs.span("vocab/finalize"):
            return vocab_lib.finalize(state)

    @functools.partial(jax.jit, static_argnums=0)
    def _build_vocab_scan(self, stacked_chunks) -> vocab_lib.VocabState:
        def body(state, chunk):
            return self.vocab_step(state, chunk), None

        state, _ = jax.lax.scan(body, self.init_state(), stacked_chunks)
        return state

    def build_vocab_scan(self, stacked_chunks) -> vocab_lib.Vocabulary:
        """Loop ① fully on device: chunks stacked on a leading axis."""
        with obs.span("loop1/scan", **self._vocab_span_labels):
            state = self._build_vocab_scan(stacked_chunks)
        with obs.span("vocab/finalize"):
            return vocab_lib.finalize(state)

    # ------------------------------------------------------------------ #
    # Loop ② — ApplyVocab + dense transforms
    # ------------------------------------------------------------------ #
    def transform_chunk(
        self, vocabulary: vocab_lib.Vocabulary, chunk
    ) -> schema_lib.ProcessedBatch:
        with jax.named_scope("piper.loop2"):
            if self._bytes_xform:
                # bytes-in loop ②: raw UTF-8 straight to the final features in
                # one dispatch (tier-routed; HBM tier falls back to decode +
                # the decoded-input chain). Bit-identical to the branch below.
                return self.compiled.transform_bytes(
                    vocabulary, chunk, max_rows=self.config.max_rows_per_chunk
                )
            return self.compiled.transform(vocabulary, self._as_batch(chunk))

    def frozen_transform(
        self, vocabulary: vocab_lib.Vocabulary
    ) -> "FrozenVocabTransform":
        """Loop ② as a standalone serving-mode step (see the class)."""
        return FrozenVocabTransform(vocabulary, pipeline=self)

    def transform_stream(
        self, vocabulary: vocab_lib.Vocabulary, chunks: Iterable
    ) -> Iterator[schema_lib.ProcessedBatch]:
        step = self.frozen_transform(vocabulary)
        for chunk in chunks:
            yield step(chunk)

    @functools.partial(jax.jit, static_argnums=0)
    def transform_scan(
        self, vocabulary: vocab_lib.Vocabulary, stacked_chunks
    ) -> schema_lib.ProcessedBatch:
        def body(carry, chunk):
            del carry
            out = self.transform_chunk(vocabulary, chunk)
            return (), out

        _, out = jax.lax.scan(body, (), stacked_chunks)
        # [n_chunks, rows, ...] — callers flatten if they need one table.
        return out

    # ------------------------------------------------------------------ #
    # End-to-end (both loops)
    # ------------------------------------------------------------------ #
    def run_stream(self, chunk_factory) -> Iterator[schema_lib.ProcessedBatch]:
        """Full two-loop run. ``chunk_factory()`` must return a fresh
        iterator each call (the dataset is streamed twice, like PIPER
        re-reading from the network/storage)."""
        vocabulary = self.build_vocab_stream(chunk_factory())
        yield from self.transform_stream(vocabulary, chunk_factory())

    def run_scan(self, stacked_chunks) -> schema_lib.ProcessedBatch:
        vocabulary = self.build_vocab_scan(stacked_chunks)
        with obs.span("loop2/scan", **self._xform_span_labels):
            return self.transform_scan(vocabulary, stacked_chunks)


class FrozenVocabTransform:
    """Loop ② factored out of the two-loop driver: frozen-vocab serving.

    Wraps a finalized :class:`vocab.Vocabulary` plus the per-chunk
    operator chain (Decode → Modulus → ApplyVocab ∥ Neg2Zero → Logarithm)
    behind one jitted callable. This is the unit of work of the *online*
    streaming service (``repro.stream``): the vocabulary was built
    offline (``PiperPipeline`` / ``ShardedPiperPipeline`` loop ①) and the
    step only ever runs loop ②, so it can serve a request stream of
    unbounded length with bounded state.

    The vocabulary can be swapped between calls (:meth:`swap_vocabulary`)
    without recompiling — tables of identical shape trace to the same
    executable — which is what makes the service's incremental vocab
    refresh a metadata-only operation.
    """

    def __init__(
        self,
        vocabulary: vocab_lib.Vocabulary,
        config: PipelineConfig | None = None,
        pipeline: "PiperPipeline | None" = None,
    ):
        if pipeline is None:
            if config is None:
                raise ValueError("need a PipelineConfig or a PiperPipeline")
            pipeline = PiperPipeline(config)
        self._pipe = pipeline
        self._vocab = vocabulary
        # Reuse the pipeline's cached jit so offline `transform_stream`
        # and a transform built from the same pipeline share executables.
        self._jit = pipeline._jit_transform_chunk

    @property
    def config(self) -> PipelineConfig:
        return self._pipe.config

    @property
    def compiled(self) -> "plan_compiler.CompiledPlan":
        """The compiled plan this transform executes (loop-② half)."""
        return self._pipe.compiled

    @property
    def vocabulary(self) -> vocab_lib.Vocabulary:
        return self._vocab

    def swap_vocabulary(self, vocabulary: vocab_lib.Vocabulary) -> None:
        """Atomically replace the frozen vocabulary (same shapes → no
        retrace). Callers serialize swaps against :meth:`__call__`; the
        streaming service applies them only between micro-batch steps."""
        self._vocab = vocabulary

    def __call__(self, chunk) -> schema_lib.ProcessedBatch:
        pipe = self._pipe
        pipe._note_chunk("loop2", chunk)
        chunk = jax.tree.map(jnp.asarray, chunk)
        with obs.span("loop2/chunk", **pipe._xform_span_labels):
            return self._jit(self._vocab, chunk)

    def compile_cache_size(self) -> int:
        """Number of compiled executables behind this step (jit cache
        entries). The scheduler's shape discipline pins this: after
        warmup it must stop growing (tests/test_stream_service.py)."""
        return self._jit._cache_size()


def flatten_processed(
    out: schema_lib.ProcessedBatch,
) -> schema_lib.ProcessedBatch:
    """[n_chunks, rows, ...] → [n_chunks*rows, ...] (keeps padding rows)."""
    flat = lambda x: x.reshape((-1,) + x.shape[2:])
    return schema_lib.ProcessedBatch(
        label=flat(out.label),
        dense=flat(out.dense),
        sparse=flat(out.sparse),
        valid=flat(out.valid),
    )
