"""Plan compiler: validate → group by signature → tier-route → emit.

Turns the declarative :class:`~repro.core.plan.PreprocPlan` IR into one
executable :class:`CompiledPlan` with the two halves every engine needs
(paper Fig. 5): a **vocab-building half** (loop ① — scatter-min
first-occurrence state over every ``GenVocab`` column, crosses included)
and a **frozen-transform half** (loop ② — the full per-chunk operator
graph). The same compiled object drives all three engines —
``PiperPipeline``, ``ShardedPiperPipeline`` (inside ``shard_map``), and
the streaming service's scheduler buckets — which is what keeps offline
and online modes executing the identical program (the tf.data-service
property).

Compilation passes
------------------
1. **Validate** against the :class:`~repro.core.schema.TableSchema`:
   every source column exists, op domains match column kinds, chains are
   well-ordered (``ApplyVocab`` needs ``GenVocab`` needs ``Modulus``;
   ``HashCross`` heads a pair-sourced chain) and params are sane. Each
   vocab column keeps its own modulus range: the
   :class:`~repro.core.vocab.VocabState` is one flat, offset-indexed
   state (``vocab.VocabLayout``). Failures raise :class:`PlanError`
   naming the offending column.
2. **Group by op-chain signature** — columns with the same canonical
   chain (decode-stage ops stripped) become one
   :class:`ColumnGroup` and execute as one vectorized ``[rows, k]``
   dispatch instead of k per-column calls.
3. **Tier-route**: every group whose chain ends ``Modulus → GenVocab →
   ApplyVocab`` (with or without a ``HashCross`` source) joins a single
   *fused route* — the whole chain plus the canonical dense group runs as
   ONE dispatch through ``ops.fused_transform``, i.e. the fused Pallas
   kernel with its VMEM/HBM residency policy (``kernels/fused_xform``).
   Remaining groups compose their ops as XLA-fused jnp stages. The
   **vocab half** gets the same treatment: every ``GenVocab`` column
   (HashCross rows included) forms one canonical group whose chain
   (uint32 Modulus → scatter-min state update) tier-routes into ONE
   ``ops.fused_vocab_update`` dispatch (kernels/fused_vocab VMEM/HBM
   policy) when the ``fused_vocab`` hint is on. The ``fused`` /
   ``fused_vocab`` / ``use_kernels`` compiler hints come from
   ``PipelineConfig``.

For ``plan.criteo_default()`` every gather/subset/assembly step below is
the identity, so the emitted program is the pre-IR hard-coded chain,
bit-for-bit (tests/test_plan.py pins this against the golden fixtures).
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro import kernels as kernels_lib
from repro.core import ops
from repro.core import plan as plan_lib
from repro.core import schema as schema_lib
from repro.core import vocab as vocab_lib


class PlanError(ValueError):
    """A :class:`~repro.core.plan.PreprocPlan` failed validation."""


# --------------------------------------------------------------------- #
# validation
# --------------------------------------------------------------------- #
def _canonical_chain(spec: plan_lib.ColumnSpec) -> tuple[plan_lib.OpSpec, ...]:
    """Strip decode-stage ops (FillMissing/Hex2Int — folded into Decode)."""
    return tuple(
        o for o in spec.ops if plan_lib.REGISTRY[o.name].stage != "decode"
    )


def _col_label(spec: plan_lib.ColumnSpec) -> str:
    return spec.name or f"{spec.kind}:{spec.source}"


def _default_range(schema: schema_lib.TableSchema, source) -> int:
    """The Modulus range a sparse column gets without a ``range`` param:
    its source column's range in the schema. A crossed pair has no one
    source column, so it takes the schema's range only when every
    column shares one."""
    if isinstance(source, tuple):
        if not schema.uniform:
            raise PlanError(
                f"sparse:{source}: a crossed column needs an explicit "
                "Modulus range when the schema's ranges differ by column"
            )
        return schema.ranges[0]
    return schema.ranges[source]


def modulus_range(
    schema: schema_lib.TableSchema, spec: plan_lib.ColumnSpec, op: plan_lib.OpSpec
):
    """The range of ``spec``'s ``Modulus`` op ``op``: its ``range`` param,
    else the schema's range for the column's source."""
    rng = op.param("range")
    return _default_range(schema, spec.source) if rng is None else rng


def validate_plan(
    plan: plan_lib.PreprocPlan, schema: schema_lib.TableSchema
) -> None:
    """Raise :class:`PlanError` unless ``plan`` is executable on ``schema``."""
    if not plan.columns:
        raise PlanError("plan has no columns")
    names = [c.name for c in plan.columns if c.name]
    if len(names) != len(set(names)):
        raise PlanError("duplicate column names in plan")
    for spec in plan.columns:
        label = _col_label(spec)
        if spec.kind not in ("dense", "sparse"):
            raise PlanError(f"{label}: unknown column kind {spec.kind!r}")
        n_src = schema.n_dense if spec.kind == "dense" else schema.n_sparse
        sources = spec.source if isinstance(spec.source, tuple) else (spec.source,)
        for s in sources:
            if not isinstance(s, int) or not 0 <= s < n_src:
                raise PlanError(
                    f"{label}: unknown column — source {s!r} not in the "
                    f"schema's {n_src} {spec.kind} columns"
                )
        seen_compute = False
        seen = {name: False for name in plan_lib.REGISTRY}
        for o in spec.ops:
            opdef = plan_lib.REGISTRY.get(o.name)
            if opdef is None:
                raise PlanError(f"{label}: unknown op {o.name!r}")
            if opdef.domain not in ("any", spec.kind):
                raise PlanError(
                    f"{label}: op {o.name} applies to {opdef.domain} columns, "
                    f"not {spec.kind}"
                )
            for k, _ in o.params:
                if k not in opdef.params:
                    raise PlanError(f"{label}: op {o.name} has no param {k!r}")
            if opdef.stage == "decode":
                if seen_compute:
                    raise PlanError(
                        f"{label}: decode-stage op {o.name} must precede "
                        "compute ops (it is folded into Decode)"
                    )
                continue
            if o.name == "HashCross":
                if seen_compute:
                    raise PlanError(
                        f"{label}: HashCross must be the first compute op"
                    )
                if not isinstance(spec.source, tuple) or len(spec.source) != 2:
                    raise PlanError(
                        f"{label}: HashCross needs a (a, b) pair source, "
                        f"got {spec.source!r}"
                    )
            seen_compute = True
            if seen[o.name] and o.name in ("Modulus", "GenVocab", "ApplyVocab"):
                raise PlanError(f"{label}: op {o.name} appears twice")
            if o.name == "GenVocab" and not seen["Modulus"]:
                raise PlanError(f"{label}: GenVocab requires a preceding Modulus")
            if o.name == "ApplyVocab" and not seen["GenVocab"]:
                raise PlanError(f"{label}: ApplyVocab requires a preceding GenVocab")
            if o.name == "Modulus":
                rng = modulus_range(schema, spec, o)
                if not isinstance(rng, int) or rng <= 0:
                    raise PlanError(f"{label}: Modulus range must be a positive int")
            if o.name in ("Clip", "MinMaxScale"):
                lo, hi = o.param("lo"), o.param("hi")
                if lo is None or hi is None or not float(hi) > float(lo):
                    raise PlanError(f"{label}: {o.name} needs params lo < hi")
            if o.name == "Bucketize":
                bnd = o.param("boundaries")
                if not bnd or list(bnd) != sorted(set(float(x) for x in bnd)):
                    raise PlanError(
                        f"{label}: Bucketize boundaries must be a non-empty "
                        "strictly-increasing tuple"
                    )
            seen[o.name] = True
        if isinstance(spec.source, tuple) and not any(
            o.name == "HashCross" for o in spec.ops
        ):
            raise PlanError(
                f"{label}: a pair source needs a HashCross op to combine it"
            )


# --------------------------------------------------------------------- #
# grouping
# --------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class ColumnGroup:
    """Columns sharing one canonical op-chain signature — one dispatch.

    ``out_slots`` are output column indices within the group's kind (plan
    order); ``sources`` are the matching input descriptors (int index or
    an ``(a, b)`` HashCross pair); ``route`` records where the compiler
    sent the group (``"fused/vmem"``, ``"fused/hbm"``, or ``"xla"``).
    """

    kind: str
    signature: tuple[plan_lib.OpSpec, ...]
    out_slots: tuple[int, ...]
    sources: tuple[object, ...]
    route: str = "xla"

    def describe(self) -> str:
        chain = " → ".join(str(o) for o in self.signature) or "(identity)"
        return (
            f"[{self.kind} ×{len(self.out_slots)} → {self.route}] {chain} "
            f"(out {list(self.out_slots)})"
        )


def _group_specs(
    specs: tuple[plan_lib.ColumnSpec, ...]
) -> list[tuple[tuple[plan_lib.OpSpec, ...], list[int], list[object]]]:
    groups: dict[tuple, tuple[list[int], list[object]]] = {}
    for slot, spec in enumerate(specs):
        sig = _canonical_chain(spec)
        slots, sources = groups.setdefault(sig, ([], []))
        slots.append(slot)
        sources.append(spec.source)
    return [(sig, s, src) for sig, (s, src) in groups.items()]


def _is_vocab_apply(sig: tuple[plan_lib.OpSpec, ...]) -> bool:
    """Chain ends ``Modulus → GenVocab → ApplyVocab`` (opt. HashCross head)."""
    names = [o.name for o in sig]
    return names in (
        ["Modulus", "GenVocab", "ApplyVocab"],
        ["HashCross", "Modulus", "GenVocab", "ApplyVocab"],
    )


def _is_dense_canonical(sig: tuple[plan_lib.OpSpec, ...]) -> bool:
    return [o.name for o in sig] == ["Neg2Zero", "Logarithm"]


def _ranges_label(ranges: tuple[int, ...]) -> str:
    if len(set(ranges)) <= 1:
        return str(ranges[0]) if ranges else "-"
    return f"{min(ranges)}..{max(ranges)} (Σ {sum(ranges)})"


# --------------------------------------------------------------------- #
# the compiled program
# --------------------------------------------------------------------- #
class CompiledPlan:
    """One jit-able program: loop-① ``vocab_step`` + loop-② ``transform``.

    Built by :func:`compile_plan`; engines hold one instance and jit its
    bound methods (the instance closes over only static routing data, so
    it is a valid static jit argument). All array work is jnp — the
    methods trace cleanly inside ``jax.jit``, ``lax.scan``, and
    ``shard_map`` bodies alike.
    """

    def __init__(
        self,
        plan: plan_lib.PreprocPlan,
        schema: schema_lib.TableSchema,
        *,
        fused: bool,
        use_kernels: bool,
        fused_vocab: bool = False,
        fused_decode: bool = False,
        track_counts: bool = False,
        vocab_slab_range: int | None = None,
    ):
        validate_plan(plan, schema)
        self.plan = plan
        self.schema = schema
        self.fused = fused
        self.fused_vocab = fused_vocab
        self.fused_decode = fused_decode
        self.use_kernels = use_kernels
        self.track_counts = track_counts
        self.vocab_slab_range = vocab_slab_range
        self.n_dense_out = plan.n_dense_out
        self.n_sparse_out = plan.n_sparse_out

        sparse_specs = plan.specs("sparse")
        dense_specs = plan.specs("dense")

        # vocab rows: every GenVocab column, in plan (sparse-slot) order.
        self._vocab_sources: tuple[object, ...] = tuple(
            spec.source
            for spec in sparse_specs
            if any(o.name == "GenVocab" for o in spec.ops)
        )
        self.n_vocab_columns = len(self._vocab_sources)
        # each vocab row's own Modulus range, in vocab-row order
        ranges: list[int] = []
        vocab_row_of: dict[int, int] = {}
        for slot, spec in enumerate(sparse_specs):
            chain = _canonical_chain(spec)
            if any(o.name == "GenVocab" for o in chain):
                mod = next(o for o in chain if o.name == "Modulus")
                vocab_row_of[slot] = len(ranges)
                ranges.append(int(modulus_range(schema, spec, mod)))
        self.vocab_ranges: tuple[int, ...] = tuple(ranges)
        # The one state layout of every engine and path (the loop-① tier
        # policy sizes its slabs).
        self.vocab_layout = vocab_lib.layout(
            self.vocab_ranges,
            track_counts=track_counts,
            slab_range=vocab_slab_range,
        )

        # group by signature, then route: vocab-apply groups merge into the
        # single fused dispatch; everything else composes as XLA stages.
        sparse_groups = _group_specs(sparse_specs)
        dense_groups = _group_specs(dense_specs)
        # the fused dispatch's sparse width (ApplyVocab columns only — a
        # GenVocab-without-ApplyVocab column adds a vocab row but never
        # enters the gather)
        self._n_apply_columns = sum(
            len(slots) for sig, slots, _ in sparse_groups if _is_vocab_apply(sig)
        )
        # The fused kernel carries sparse AND dense tiles; with no
        # canonical dense group its degenerate-width guard would fall all
        # the way back to the jnp oracle while the route labels claimed
        # "fused" — so the fused dispatch requires both halves, and plans
        # without one run the (kernel-dispatched) unfused chain instead.
        has_canonical_dense = any(
            _is_dense_canonical(sig) for sig, _, _ in dense_groups
        )
        # On a TPU the VMEM tier of the fused loop-② kernel stays off:
        # Mosaic refuses its in-kernel take_along_axis table gather (an
        # AssertionError inside the lowering, JAX 0.9;
        # tests/test_tpu_compile.py), so that tier runs the unfused XLA
        # chain there and its route label says so (ROADMAP speed item 2).
        self._fused_dispatch = (
            fused
            and self._n_apply_columns > 0
            and has_canonical_dense
            and not (self.tier == "vmem" and kernels_lib.on_tpu())
        )
        # Loop ①'s single canonical group is "every GenVocab column"
        # (crosses materialize at gather time and join the same rows), so
        # the whole vocab half tier-routes as ONE fused dispatch whenever
        # the hint is on and there is state to build.
        self._fused_vocab_dispatch = fused_vocab and self.n_vocab_columns > 0
        apply_slots: list[int] = []
        apply_sources: list[object] = []
        apply_rows: list[int] = []
        self._sparse_xla: list[tuple[tuple, tuple, tuple]] = []
        self.groups: list[ColumnGroup] = []
        for sig, slots, sources in sparse_groups:
            if _is_vocab_apply(sig):
                apply_slots.extend(slots)
                apply_sources.extend(sources)
                apply_rows.extend(vocab_row_of[s] for s in slots)
                route = f"fused/{self.tier}" if self._fused_dispatch else "unfused"
            else:
                self._sparse_xla.append((sig, tuple(slots), tuple(sources)))
                route = "xla"
            self.groups.append(
                ColumnGroup("sparse", sig, tuple(slots), tuple(sources), route)
            )
        self._apply_slots = tuple(apply_slots)
        self._apply_sources = tuple(apply_sources)
        self._apply_vocab_rows = tuple(apply_rows)

        fused_dense_slots: list[int] = []
        fused_dense_sources: list[int] = []
        self._dense_xla: list[tuple[tuple, tuple, tuple]] = []
        for sig, slots, sources in dense_groups:
            # the canonical dense chain rides the fused dispatch only when a
            # vocab-apply group exists to share it with; standalone it still
            # runs the (kernel-dispatched) fused dense pass.
            if _is_dense_canonical(sig) and self._apply_slots:
                fused_dense_slots.extend(slots)
                fused_dense_sources.extend(sources)
                route = f"fused/{self.tier}" if self._fused_dispatch else "unfused"
            else:
                self._dense_xla.append((sig, tuple(slots), tuple(sources)))
                route = "xla"
            self.groups.append(
                ColumnGroup("dense", sig, tuple(slots), tuple(sources), route)
            )
        self._fused_dense_slots = tuple(fused_dense_slots)
        self._fused_dense_sources = tuple(fused_dense_sources)

        # Bytes-in routing (kernels/fused_decode_*): the decode kernels
        # scatter every schema column straight into the state / output
        # table, so they only apply when the plan is the *identity over
        # the wire layout* — no crossed/subset/permuted sources, every
        # sparse column a vocab column, the canonical dense chain on
        # every dense column, nothing routed to XLA stages. Anything
        # fancier keeps the decoded-input paths (which the bytes-in
        # wrappers also fall back to on the HBM tier).
        identity_sparse = tuple(range(schema.n_sparse))
        identity_dense = tuple(range(schema.n_dense))
        # the bytes-in kernels take the rectangular view of one range
        uniform = self.vocab_layout.uniform
        self.decode_vocab_dispatch = (
            fused_decode
            and uniform
            and schema.n_sparse > 0
            and self._vocab_sources == identity_sparse
            # the bytes-in kernel carries no count plane
            and not track_counts
        )
        self.decode_xform_dispatch = (
            fused_decode
            and uniform
            and schema.n_sparse > 0
            and schema.n_dense > 0
            and self.n_sparse_out == schema.n_sparse
            and self.n_dense_out == schema.n_dense
            and self._apply_slots == tuple(range(self.n_sparse_out))
            and self._apply_sources == identity_sparse
            and self._apply_vocab_rows == tuple(range(schema.n_sparse))
            and self._fused_dense_slots == tuple(range(self.n_dense_out))
            and self._fused_dense_sources == identity_dense
            and not self._sparse_xla
            and not self._dense_xla
        )

    # -- metadata ------------------------------------------------------ #
    @property
    def tier(self) -> str:
        """Memory tier of the vocab-apply dispatch (paper §3.2/§4.4.6) —
        computed from the flat table the fused gather reads."""
        from repro.kernels.fused_xform import ops as fx_ops

        return fx_ops.fused_tier(self.vocab_layout)

    @property
    def vocab_tier(self) -> str:
        """Memory tier of the loop-① state dispatch — computed from the
        rows the ``VocabState`` accumulator actually carries (crosses
        included, count plane included), so it matches what
        ``fused_vocab_tier()`` picks at runtime."""
        from repro.kernels.fused_vocab import ops as fv_ops

        return fv_ops.fused_vocab_tier(
            self.vocab_layout.ranges,
            slab_range=self.vocab_slab_range,
            track_counts=self.track_counts,
        )

    @property
    def vocab_slabs(self) -> int:
        """How many state slabs loop ① streams per chunk (1 off the
        hbm_slab tier) — the obs spans tag dispatches with it."""
        return self.vocab_layout.n_slabs if self.vocab_tier == "hbm_slab" else 1

    @property
    def vocab_route(self) -> str:
        """Where the compiler sent the vocab-building half:
        ``"fused/vmem"``, ``"fused/hbm_slab"``, or ``"unfused"``."""
        if self._fused_vocab_dispatch:
            return f"fused/{self.vocab_tier}"
        return "unfused"

    @property
    def xform_route(self) -> str:
        """Where the compiler sent the canonical loop-② half:
        ``"fused/vmem"``, ``"fused/hbm"``, or ``"unfused"`` — the label
        the obs spans tag loop-② dispatches with."""
        if self._fused_dispatch:
            return f"fused/{self.tier}"
        return "unfused"

    @property
    def decode_vocab_route(self) -> str:
        """Where a utf8 engine's loop ① enters: ``"bytes/vmem"`` (the
        bytes-in kernel), ``"bytes/hbm_slab"`` (bytes-in requested but
        the state over-budget — ref decode + the tier-routed
        decoded-input chain), or ``"decoded"`` (decode runs as its own
        dispatch)."""
        if self.decode_vocab_dispatch:
            return f"bytes/{self.vocab_tier}"
        return "decoded"

    def decode_xform_route(self, max_rows: int) -> str:
        """Where a utf8 engine's loop ② enters for a given chunk row
        capacity (the output table shares the VMEM budget, and
        ``max_rows`` is per-engine — stream buckets shrink it)."""
        if not self.decode_xform_dispatch:
            return "decoded"
        from repro.kernels.fused_decode_xform import ops as fdx_ops

        return "bytes/" + fdx_ops.fused_decode_tier(
            self.schema.n_dense,
            self.schema.n_sparse,
            self.vocab_ranges[0],
            max_rows,
        )

    def static_routes(self, *, max_rows: int | None = None) -> dict:
        """Structured route + VMEM-footprint metadata for every dispatch
        the compiled program can issue — the single source
        ``repro.analysis.kernelcheck`` consumes instead of re-deriving
        widths from the plan. Each entry pairs the route label the obs
        spans use with the kernel package's declared ``vmem_accounting``
        and the budget its tier guard charges it against.

        ``max_rows`` adds the ``decode_xform`` entry (that tier depends
        on the per-engine chunk row capacity)."""
        from repro.kernels.fused_decode_vocab import ops as fdv_ops
        from repro.kernels.fused_decode_xform import ops as fdx_ops
        from repro.kernels.fused_vocab import ops as fv_ops
        from repro.kernels.fused_xform import ops as fx_ops

        n_apply = max(self._n_apply_columns, 1)
        n_vocab = max(self.n_vocab_columns, 1)
        vocab_tier = self.vocab_tier
        lay = self.vocab_layout
        widest = max(lay.ranges, default=1)
        routes = {
            "xform": {
                "route": self.xform_route,
                "tier": self.tier,
                "n_columns": n_apply,
                "vocab_range": widest,
                "ranges": lay.ranges,
                "footprint": fx_ops.vmem_accounting(
                    lay, n_apply, n_dense=len(self._fused_dense_slots)
                ),
                "carried": ("table_stack",),
                "budget": fx_ops.FUSED_TABLE_VMEM_BYTES,
            },
            "vocab": {
                "route": self.vocab_route,
                "tier": vocab_tier,
                "n_columns": n_vocab,
                "vocab_range": widest,
                "ranges": lay.ranges,
                "slabs": self.vocab_slabs,
                "footprint": fv_ops.vmem_accounting(
                    lay, track_counts=self.track_counts
                ),
                "carried": ("state_stack", "counts_stack"),
                "budget": (
                    fv_ops.SLAB_VMEM_BYTES
                    if vocab_tier == "hbm_slab"
                    else fv_ops.FUSED_STATE_VMEM_BYTES
                ),
            },
            "decode_vocab": {
                "route": self.decode_vocab_route,
                "tier": self.vocab_tier,
                "n_columns": n_vocab,
                "vocab_range": widest,
                "ranges": lay.ranges,
                "footprint": fdv_ops.vmem_accounting(n_vocab, widest),
                "carried": ("state_stack",),
                "budget": fv_ops.FUSED_STATE_VMEM_BYTES,
            },
        }
        if max_rows is not None:
            routes["decode_xform"] = {
                "route": self.decode_xform_route(max_rows),
                "tier": self.decode_xform_route(max_rows).split("/")[-1],
                "n_columns": self.schema.n_sparse,
                "vocab_range": widest,
                "ranges": lay.ranges,
                "footprint": fdx_ops.vmem_accounting(
                    self.schema.n_dense, self.schema.n_sparse, widest, max_rows
                ),
                "carried": ("table_stack", "out_table"),
                "budget": fx_ops.FUSED_TABLE_VMEM_BYTES,
            }
        return routes

    def describe(self) -> str:
        head = (
            f"CompiledPlan: {self.n_dense_out} dense + {self.n_sparse_out} "
            f"sparse out, {self.n_vocab_columns} vocab columns @ range "
            f"{_ranges_label(self.vocab_ranges)}, fused={self.fused} "
            f"(dispatch={self.xform_route})"
        )
        vocab_half = (
            f"[vocab ×{self.n_vocab_columns} → {self.vocab_route}] "
            "Modulus → GenVocab (loop ① scatter-min)"
        )
        decode_half = (
            f"[decode → loop① {self.decode_vocab_route}, loop② "
            f"{'bytes' if self.decode_xform_dispatch else 'decoded'}] "
            "utf8 bytes-in fusion (kernels/fused_decode_*)"
        )
        return "\n".join(
            [head, vocab_half, decode_half] + [g.describe() for g in self.groups]
        )

    # -- gather / subset / assembly helpers ---------------------------- #
    def _gather_sparse(self, sparse: jnp.ndarray, sources: tuple) -> jnp.ndarray:
        """[rows, n_sparse] input → [rows, len(sources)] in source order;
        pair sources materialize their HashCross column. Identity sources
        return the input array unchanged (no-op for criteo_default)."""
        if sources == tuple(range(sparse.shape[1])):
            return sparse
        if not sources:
            return sparse[:, :0]
        parts = []
        for s in sources:
            if isinstance(s, tuple):
                parts.append(ops.hash_cross(sparse[:, s[0]], sparse[:, s[1]])[:, None])
            else:
                parts.append(sparse[:, s : s + 1])
        return jnp.concatenate(parts, axis=1)

    def _gather_dense(self, dense: jnp.ndarray, sources: tuple) -> jnp.ndarray:
        if sources == tuple(range(dense.shape[1])):
            return dense
        if not sources:
            return dense[:, :0]
        return dense[:, np.asarray(sources, np.int32)]

    def _apply_columns(self) -> tuple[int, ...] | None:
        """The vocabulary columns the fused gather reads, or None when
        they are every column in order (no-op for criteo_default)."""
        rows = self._apply_vocab_rows
        return None if rows == tuple(range(self.n_vocab_columns)) else rows

    @staticmethod
    def _assemble(pieces, n_out: int, rows, dtype) -> jnp.ndarray:
        """Scatter group outputs back to plan column order. A single piece
        already covering every slot in order passes through untouched."""
        if len(pieces) == 1 and pieces[0][0] == tuple(range(n_out)):
            return pieces[0][1].astype(dtype)
        cols: list = [None] * n_out
        for slots, mat in pieces:
            for j, slot in enumerate(slots):
                cols[slot] = mat[:, j].astype(dtype)
        if not cols:
            return jnp.zeros((rows, 0), dtype)
        return jnp.stack(cols, axis=1)

    # -- op evaluation for XLA-routed groups --------------------------- #
    def _eval_sparse(self, raw: jnp.ndarray, sig, sources) -> jnp.ndarray:
        x = raw
        for o in sig:
            if o.name == "HashCross":
                pass  # applied at gather time (pair sources)
            elif o.name == "Modulus":
                # default = each source column's schema range, matching
                # validate_plan — NOT the vocab columns' (possibly
                # overridden) range.
                rng = o.param("range")
                x = ops.positive_modulus(
                    x,
                    tuple(_default_range(self.schema, s) for s in sources)
                    if rng is None
                    else int(rng),
                )
            elif o.name == "GenVocab":
                pass  # loop-①-only (the column emits its modded values)
            else:
                # ApplyVocab chains route to the fused dispatch; anything
                # else is a registry op this compiler does not yet lower —
                # fail loudly instead of serving un-transformed values.
                raise PlanError(f"unhandled sparse op {o.name} in compiler")
        return x

    def _eval_dense(self, raw: jnp.ndarray, sig) -> jnp.ndarray:
        names = [o.name for o in sig]
        if names == ["Neg2Zero", "Logarithm"]:
            # the canonical pair keeps its kernel-dispatched fused pass
            return ops.dense_transform(raw, use_kernel=self.use_kernels)
        x = raw.astype(jnp.float32)
        for o in sig:
            if o.name == "Neg2Zero":
                x = ops.neg2zero(x)
            elif o.name == "Logarithm":
                x = ops.logarithm(x)
            elif o.name == "Clip":
                x = ops.clip(x, float(o.param("lo")), float(o.param("hi")))
            elif o.name == "MinMaxScale":
                x = ops.minmax_scale(x, float(o.param("lo")), float(o.param("hi")))
            elif o.name == "Bucketize":
                x = ops.bucketize(x, tuple(o.param("boundaries")))
            else:
                raise PlanError(f"unhandled dense op {o.name} in compiler")
        return x

    # -- loop ① — vocab-building half ---------------------------------- #
    def init_state(self) -> vocab_lib.VocabState:
        return vocab_lib.VocabState.init(
            self.vocab_layout, track_counts=self.track_counts
        )

    def vocab_step(
        self, state: vocab_lib.VocabState, batch: schema_lib.TabularBatch
    ) -> vocab_lib.VocabState:
        """Absorb one decoded chunk into the first-occurrence state —
        every GenVocab column (crosses materialized first), one scatter.

        With the ``fused_vocab`` hint the whole chain (uint32 Modulus →
        scatter-min) runs as ONE tier-routed dispatch through
        ``ops.fused_vocab_update`` (kernels/fused_vocab): the modded
        matrix never materializes to HBM between the modulus and the
        state update — loop ①'s half of Piper's on-chip dataflow, bit-
        identical to the unfused chain below on every path."""
        raw = self._gather_sparse(batch.sparse, self._vocab_sources)
        if self._fused_vocab_dispatch:
            return ops.fused_vocab_update(
                state, raw, batch.valid, slab_range=self.vocab_slab_range
            )
        modded = ops.positive_modulus(raw, self.vocab_ranges)
        if self.use_kernels:
            from repro.kernels.vocab import ops as vocab_ops

            return vocab_ops.genvocab_update(state, modded, batch.valid)
        return vocab_lib.update(state, modded, batch.valid)

    def vocab_step_bytes(
        self,
        state: vocab_lib.VocabState,
        byte_buf: jnp.ndarray,
        *,
        max_rows: int,
    ) -> vocab_lib.VocabState:
        """Loop ① straight from a raw UTF-8 chunk — Decode → Modulus →
        scatter-min as ONE tier-routed dispatch (kernels/fused_decode_
        vocab). Only valid when :attr:`decode_vocab_dispatch` is set (the
        plan is the identity over the wire layout); bit-identical to
        ``vocab_step`` on the decoded chunk."""
        return ops.fused_decode_vocab_update(
            state,
            byte_buf,
            n_fields=self.schema.n_fields,
            n_dense=self.schema.n_dense,
            n_sparse=self.schema.n_sparse,
            max_rows=max_rows,
        )

    def transform_bytes(
        self,
        vocabulary: vocab_lib.Vocabulary,
        byte_buf: jnp.ndarray,
        *,
        max_rows: int,
    ) -> schema_lib.ProcessedBatch:
        """Loop ② straight from a raw UTF-8 chunk — Decode → Modulus →
        ApplyVocab ∥ Neg2Zero → Logarithm as ONE tier-routed dispatch
        (kernels/fused_decode_xform). Only valid when
        :attr:`decode_xform_dispatch` is set; ids/labels bit-identical
        and dense identical-formula to ``transform`` on the decoded
        chunk, padding rows included."""
        # the dispatch requires the identity plan: every column in order
        label, dense, ids, valid = ops.fused_decode_transform(
            vocabulary,
            byte_buf,
            n_fields=self.schema.n_fields,
            n_dense=self.schema.n_dense,
            n_sparse=self.schema.n_sparse,
            max_rows=max_rows,
        )
        return schema_lib.ProcessedBatch(
            label=label, dense=dense, sparse=ids, valid=valid
        )

    # -- loop ② — frozen-transform half -------------------------------- #
    def transform(
        self, vocabulary: vocab_lib.Vocabulary, batch: schema_lib.TabularBatch
    ) -> schema_lib.ProcessedBatch:
        """The whole per-chunk operator graph with a frozen vocabulary."""
        rows = batch.sparse.shape[0]
        sparse_pieces, dense_pieces = [], []

        if self._apply_slots:
            sp_in = self._gather_sparse(batch.sparse, self._apply_sources)
            de_in = self._gather_dense(batch.dense, self._fused_dense_sources)
            columns = self._apply_columns()
            if self._fused_dispatch:
                # Piper's dataflow: the whole chain in one on-chip pass —
                # no modded/ids/dense intermediates round-tripping HBM.
                ids, dfx = ops.fused_transform(
                    vocabulary, sp_in, de_in, columns=columns
                )
            else:
                modded = ops.positive_modulus(
                    sp_in, ops.column_ranges(vocabulary.ranges, columns)
                )
                ids = ops.apply_vocab(
                    vocabulary, modded, use_kernel=self.use_kernels, columns=columns
                )
                dfx = ops.dense_transform(de_in, use_kernel=self.use_kernels)
            sparse_pieces.append((self._apply_slots, ids))
            if self._fused_dense_slots:
                dense_pieces.append((self._fused_dense_slots, dfx))

        for sig, slots, sources in self._sparse_xla:
            raw = self._gather_sparse(batch.sparse, sources)
            sparse_pieces.append((slots, self._eval_sparse(raw, sig, sources)))
        for sig, slots, sources in self._dense_xla:
            raw = self._gather_dense(batch.dense, sources)
            dense_pieces.append((slots, self._eval_dense(raw, sig)))

        return schema_lib.ProcessedBatch(
            label=batch.label,
            dense=self._assemble(dense_pieces, self.n_dense_out, rows, jnp.float32),
            sparse=self._assemble(sparse_pieces, self.n_sparse_out, rows, jnp.int32),
            valid=batch.valid,
        )


def compile_plan(
    plan: plan_lib.PreprocPlan,
    schema: schema_lib.TableSchema,
    *,
    fused: bool | None = None,
    use_kernels: bool = False,
    fused_vocab: bool | None = None,
    fused_decode: bool | None = None,
    track_counts: bool = False,
    vocab_slab_range: int | None = None,
) -> CompiledPlan:
    """Validate + group + route ``plan`` into a :class:`CompiledPlan`.

    ``fused`` is the resolved ``PipelineConfig.use_fused_kernel`` hint
    (``None`` re-resolves via ``kernels.resolve_fused()``) for the
    loop-② transform half; ``fused_vocab`` is the matching
    ``PipelineConfig.use_fused_vocab`` hint for the loop-① vocab half
    (same ``None`` resolution); ``fused_decode`` is the matching
    ``PipelineConfig.use_fused_decode`` hint for the bytes-in whole-
    pipeline dispatches (utf8 feeds only — the engines consult the
    routing, the compiler just records admissibility; ``None`` resolves
    to **off** — Mosaic refuses the bytes-in kernels — mirroring
    ``PipelineConfig.fused_decode_enabled``); ``use_kernels`` routes
    the unfused per-op stages through their Pallas kernels.
    ``track_counts`` builds the state with the occurrence-count plane
    (``PipelineConfig.track_vocab_counts`` — required by the capped
    finalizers); ``vocab_slab_range`` forces loop ①'s hbm_slab tier
    with that per-column slab width.
    """
    if fused is None or fused_vocab is None:
        resolved = kernels_lib.resolve_fused()
        fused = resolved if fused is None else fused
        fused_vocab = resolved if fused_vocab is None else fused_vocab
    if fused_decode is None:
        fused_decode = False
    return CompiledPlan(
        plan,
        schema,
        fused=bool(fused),
        use_kernels=use_kernels,
        fused_vocab=bool(fused_vocab),
        fused_decode=bool(fused_decode),
        track_counts=bool(track_counts),
        vocab_slab_range=vocab_slab_range,
    )
