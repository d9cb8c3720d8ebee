"""jaxpr audit — static dispatch counting and hot-path hygiene.

Traces the compiled plan's hot-path entry points
(``CompiledPlan.vocab_step`` / ``transform`` and their bytes-in
variants) with abstract inputs — no device execution — and audits the
resulting jaxprs:

  * **dispatch counts** (``count_dispatches``, the one shared
    implementation the fused-kernel benchmarks import, so benchmark
    pins and the analyzer can never disagree): primitives per chunk
    before XLA fusion, pjit/call wrappers descended into, a
    ``pallas_call`` counting as ONE launch. JX303 (error) fires when a
    fused route fails to issue strictly fewer dispatches than its
    unfused counterpart — the paper's no-materialization property,
    statically enforced;
  * **host callbacks** (JX301, error): any ``*callback*`` primitive —
    ``pure_callback``, ``io_callback``, ``debug_callback`` — anywhere
    in a hot-path jaxpr means a device→host round-trip per chunk;
  * **donation misses** (JX310, warning): an AST scan of
    ``repro.train`` for ``jax.jit`` calls on train-step factories
    without ``donate_argnums``/``donate_argnames`` — the params and
    opt_state buffers would copy every step instead of updating in
    place (``make_tabular_train_step``'s documented contract).
"""

from __future__ import annotations

import ast
import glob
import os

import jax
import jax.numpy as jnp

from repro.analysis.findings import Finding
from repro.core import schema as schema_lib
from repro.core import vocab as vocab_lib

# call-like wrappers that are pure structure (inlined by XLA), not work:
# descend into their bodies instead of counting them ("jit" is the nested
# jit's primitive name since JAX 0.7, "pjit" before)
_CALL_PRIMS = ("jit", "pjit", "closed_call", "core_call", "custom_jvp_call")


def count_dispatches(fn, *args) -> int:
    """Primitive count of ``fn``'s jaxpr. pjit/call wrappers are
    descended into (they are structure, not work); everything else —
    including a ``pallas_call``, which is ONE kernel launch no matter
    how long the on-chip chain inside it is — counts as one dispatch."""

    def count(jaxpr) -> int:
        n = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in _CALL_PRIMS:
                sub = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
                n += count(getattr(sub, "jaxpr", sub))
            else:
                n += 1
        return n

    return count(jax.make_jaxpr(fn)(*args).jaxpr)


def _sub_jaxprs(eqn):
    """Every jaxpr nested in an eqn's params (pjit, scan, while, cond,
    custom_* — any param that is or contains a jaxpr)."""
    for v in eqn.params.values():
        vs = v if isinstance(v, (list, tuple)) else [v]
        for item in vs:
            inner = getattr(item, "jaxpr", None)
            if inner is not None and hasattr(inner, "eqns"):
                yield inner
            elif hasattr(item, "eqns"):
                yield item


def find_callbacks(fn, *args) -> list[str]:
    """Names of every callback primitive reachable from ``fn``'s jaxpr."""
    hits: list[str] = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if "callback" in eqn.primitive.name:
                hits.append(eqn.primitive.name)
            for sub in _sub_jaxprs(eqn):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return hits


# --------------------------------------------------------------------- #
# hot-path tracing
# --------------------------------------------------------------------- #
def _abstract_batch(schema: schema_lib.TableSchema, rows: int):
    sds = jax.ShapeDtypeStruct
    return schema_lib.TabularBatch(
        label=sds((rows,), jnp.int32),
        dense=sds((rows, schema.n_dense), jnp.int32),
        sparse=sds((rows, schema.n_sparse), jnp.int32),
        valid=sds((rows,), jnp.bool_),
    )


def _abstract_state(compiled):
    sds = jax.ShapeDtypeStruct
    lay = compiled.vocab_layout
    return vocab_lib.VocabState(
        first_pos=sds((lay.entries,), jnp.int32),
        rows_seen=sds((), jnp.int32),
        counts=sds((lay.entries,), jnp.int32) if compiled.track_counts else None,
        layout=lay,
    )


def _abstract_vocab(compiled):
    sds = jax.ShapeDtypeStruct
    lay = compiled.vocab_layout
    return vocab_lib.Vocabulary(
        table=sds((lay.entries,), jnp.int32),
        sizes=sds((lay.n_columns,), jnp.int32),
        layout=lay,
    )


def audit_compiled_plan(
    compiled,
    *,
    rows: int = 256,
    max_rows: int | None = None,
    context: str = "plan",
) -> tuple[list[Finding], dict[str, int]]:
    """Trace every hot-path entry point; → (findings, dispatch stats)."""
    out: list[Finding] = []
    stats: dict[str, int] = {}
    schema = compiled.schema
    batch = _abstract_batch(schema, rows)
    state = _abstract_state(compiled)
    vocabulary = _abstract_vocab(compiled)
    sds = jax.ShapeDtypeStruct
    targets: list[tuple[str, object, tuple]] = [
        ("vocab_step", compiled.vocab_step, (state, batch)),
        ("transform", compiled.transform, (vocabulary, batch)),
    ]
    if max_rows is not None:
        byte_buf = sds((schema.max_row_bytes * rows,), jnp.uint8)
        if compiled.decode_vocab_dispatch:
            targets.append(
                (
                    "vocab_step_bytes",
                    lambda s, b: compiled.vocab_step_bytes(
                        s, b, max_rows=max_rows
                    ),
                    (state, byte_buf),
                )
            )
        if compiled.decode_xform_dispatch:
            targets.append(
                (
                    "transform_bytes",
                    lambda v, b: compiled.transform_bytes(
                        v, b, max_rows=max_rows
                    ),
                    (vocabulary, byte_buf),
                )
            )
    for name, fn, args in targets:
        obj = f"{context}/{name}"
        try:
            stats[obj] = count_dispatches(fn, *args)
            callbacks = find_callbacks(fn, *args)
        except Exception as e:  # trace failure is itself a finding
            out.append(
                Finding(
                    rule="JX302",
                    severity="error",
                    pass_name="jaxpr",
                    file="src/repro/core/plan_compiler.py",
                    line=0,
                    obj=obj,
                    message=f"hot-path trace failed: {type(e).__name__}: {e}",
                )
            )
            continue
        for prim in sorted(set(callbacks)):
            out.append(
                Finding(
                    rule="JX301",
                    severity="error",
                    pass_name="jaxpr",
                    file="src/repro/core/plan_compiler.py",
                    line=0,
                    obj=obj,
                    message=(
                        f"host callback primitive {prim!r} on the hot path "
                        f"({callbacks.count(prim)}×) — a device→host "
                        "round-trip per chunk"
                    ),
                )
            )
    return out, stats


def check_fused_reduction(*, rows: int = 256) -> tuple[list[Finding], dict]:
    """The no-materialization property, statically: each fused route must
    issue strictly fewer dispatches per chunk than its unfused twin."""
    from repro.core import plan as plan_lib
    from repro.core import plan_compiler

    out: list[Finding] = []
    stats: dict[str, int] = {}
    schema = schema_lib.CRITEO
    plan = plan_lib.criteo_default(schema)

    def build(**kw):
        return plan_compiler.compile_plan(plan, schema, **kw)

    fused = build(fused=True, fused_vocab=True)
    unfused = build(fused=False, fused_vocab=False)
    batch = _abstract_batch(schema, rows)
    pairs = [
        (
            "vocab_step",
            (fused.vocab_step, (_abstract_state(fused), batch)),
            (unfused.vocab_step, (_abstract_state(unfused), batch)),
        ),
        (
            "transform",
            (fused.transform, (_abstract_vocab(fused), batch)),
            (unfused.transform, (_abstract_vocab(unfused), batch)),
        ),
    ]
    for name, (ffn, fargs), (ufn, uargs) in pairs:
        d_fused = count_dispatches(ffn, *fargs)
        d_unfused = count_dispatches(ufn, *uargs)
        stats[f"fused/{name}"] = d_fused
        stats[f"unfused/{name}"] = d_unfused
        if d_fused >= d_unfused:
            out.append(
                Finding(
                    rule="JX303",
                    severity="error",
                    pass_name="jaxpr",
                    file="src/repro/core/plan_compiler.py",
                    line=0,
                    obj=f"criteo-5k/{name}",
                    message=(
                        f"fused route issues {d_fused} dispatches per "
                        f"chunk vs {d_unfused} unfused — fusion must "
                        "strictly reduce the count"
                    ),
                )
            )
    return out, stats


# --------------------------------------------------------------------- #
# donation audit (AST — no tracing needed)
# --------------------------------------------------------------------- #
def audit_donation_source(
    src: str, path: str, *, root: str | None = None
) -> list[Finding]:
    """Flag ``jax.jit(...)`` calls on train-step callables that donate
    neither argnums nor argnames — the params/opt_state buffers copy."""
    out: list[Finding] = []
    rel = path if root is None else os.path.relpath(path, root)
    tree = ast.parse(src)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        is_jit = (
            isinstance(fn, ast.Attribute) and fn.attr == "jit"
        ) or (isinstance(fn, ast.Name) and fn.id == "jit")
        if not is_jit or not node.args:
            continue
        target_src = ast.unparse(node.args[0])
        if "step" not in target_src:
            continue  # only step-shaped jits carry the donation contract
        kw_names = {k.arg for k in node.keywords}
        if not kw_names & {"donate_argnums", "donate_argnames"}:
            out.append(
                Finding(
                    rule="JX310",
                    severity="warning",
                    pass_name="jaxpr",
                    file=rel,
                    line=node.lineno,
                    obj=f"jit({target_src[:40]})",
                    message=(
                        "train-step jax.jit without donate_argnums/"
                        "donate_argnames — params and opt_state copy "
                        "every step instead of updating in place"
                    ),
                )
            )
    return out


def check_repo_donation(root: str) -> list[Finding]:
    out: list[Finding] = []
    for path in sorted(glob.glob(os.path.join(root, "src/repro/train/*.py"))):
        with open(path) as f:
            out.extend(audit_donation_source(f.read(), path, root=root))
    return out


def run(root: str) -> tuple[list[Finding], dict[str, int]]:
    """The whole pass on the repo's stock configuration."""
    from repro.core import plan as plan_lib
    from repro.core import plan_compiler

    schema = schema_lib.CRITEO
    compiled = plan_compiler.compile_plan(
        plan_lib.criteo_default(schema),
        schema,
        fused=True,
        fused_vocab=True,
        fused_decode=True,
    )
    findings, stats = audit_compiled_plan(
        compiled, max_rows=1 << 14, context="criteo-5k"
    )
    reduction_findings, reduction_stats = check_fused_reduction()
    stats.update(reduction_stats)
    findings.extend(reduction_findings)
    findings.extend(check_repo_donation(root))
    return findings, stats
