"""kernelcheck — VMEM footprint vs. tier router, and a static race
detector for the kernel layer.

Two halves:

**Footprint/tier agreement.** Every ``kernels/fused_*`` package
declares its VMEM accounting in one structured place
(``vmem_accounting`` — the bytes of each resident buffer: grid-carried
table/state stacks, streamed tiles, decode carries) and the plan
compiler exposes the per-dispatch route labels plus those footprints
via ``CompiledPlan.static_routes``. This pass recomputes the residency
decision *independently* from the declared bytes and budgets
(:data:`FUSED_TABLE_VMEM_BYTES` / :data:`FUSED_STATE_VMEM_BYTES` /
:data:`SLAB_VMEM_BYTES`, the ``VMEM_TIER_MAX`` per-column cutoff) and
flags any disagreement with the router's actual decision — a ``vmem``
claim whose carried bytes exceed the budget (KC201), or a demotion to
hbm/hbm_slab when the full stack provably fits (KC202). The shape
matrix sweeps the paper's evaluation points (5K, 1M), the budget
boundary, and the tracked-counts / forced-slab variants.

**Aliasing / grid-carry audit (KC210/KC211).** An AST pass over every
``kernels/*/kernel.py`` extracts each ``pl.pallas_call``'s grid,
BlockSpec index maps, ``input_output_aliases``, and any declared
``dimension_semantics``. A block whose index map is *constant over a
grid dimension* is carried across that dimension — on TPU that is only
sound when the dimension iterates sequentially (the default
"arbitrary" order). A serial-RMW accumulator (scatter-min/scatter-add
state, recognized as an aliased input→output with a carried out block)
whose carried dimension is declared ``"parallel"`` is a data race:
KC210, error. A carried out block that is neither aliased nor seeded
by a ``pl.when`` first-step init reads undefined VMEM on its first
visit: KC211, warning.
"""

from __future__ import annotations

import ast
import dataclasses
import glob
import os

from repro.analysis.findings import Finding
from repro.core import schema as schema_lib
from repro.core import vocab as vocab_lib

VMEM_TIER_MAX = vocab_lib.VMEM_TIER_MAX


def _rel(path: str, root: str | None) -> str:
    if root and os.path.isabs(path):
        return os.path.relpath(path, root)
    return path


# --------------------------------------------------------------------- #
# footprint / tier agreement
# --------------------------------------------------------------------- #
def _carried_bytes(entry: dict) -> int:
    fp = entry["footprint"]
    return sum(fp.get(k, 0) for k in entry["carried"])


def check_routes(compiled, *, max_rows=None, context="plan") -> list[Finding]:
    """Recompute each dispatch's residency decision from the declared
    accounting and flag disagreement with the router's tier labels."""
    from repro.kernels.fused_vocab import ops as fv_ops

    out: list[Finding] = []

    def emit(rule, severity, name, message):
        out.append(
            Finding(
                rule=rule,
                severity=severity,
                pass_name="kernelcheck",
                file="src/repro/core/plan_compiler.py",
                line=0,
                obj=f"{context}/{name}",
                message=message,
            )
        )

    routes = compiled.static_routes(max_rows=max_rows)
    for name, entry in routes.items():
        tier = entry["tier"]
        carried = _carried_bytes(entry)
        vr = entry["vocab_range"]
        if tier == "vmem":
            if carried > entry["budget"] or vr > VMEM_TIER_MAX:
                emit(
                    "KC201",
                    "error",
                    name,
                    f"router picked vmem but the carried footprint "
                    f"({carried} B of {sorted(entry['carried'])}) exceeds "
                    f"the {entry['budget']} B budget or vocab_range {vr} "
                    f"exceeds the {VMEM_TIER_MAX} cutoff",
                )
            continue
        if tier in ("hbm", "hbm_slab"):
            # demotion must be forced: the full-width resident set
            # (stack at full vocab_range, counts included) must not fit.
            if name == "vocab":
                # the whole state as one resident run (no slabs)
                full_acct = fv_ops.vmem_accounting(
                    vocab_lib.VocabLayout(entry["ranges"]),
                    track_counts=compiled.track_counts,
                )
                full = full_acct["state_stack"] + full_acct.get(
                    "counts_stack", 0
                )
                resident_budget = fv_ops.FUSED_STATE_VMEM_BYTES
                forced = compiled.vocab_slab_range is not None
            elif name == "decode_vocab":
                # same accumulator and same forced-slab knob as "vocab";
                # the bytes-in wrapper just falls back off the vmem tier
                full = carried
                resident_budget = entry["budget"]
                forced = compiled.vocab_slab_range is not None
            else:
                full = carried
                resident_budget = entry["budget"]
                forced = False
            if (
                not forced
                and full <= resident_budget
                and vr <= VMEM_TIER_MAX
            ):
                emit(
                    "KC202",
                    "error",
                    name,
                    f"router demoted to {tier} but the full resident set "
                    f"({full} B) fits the {resident_budget} B budget and "
                    f"vocab_range {vr} is within the cutoff",
                )
            # the slab-block bound only constrains the dispatch that
            # actually streams slabs (the decoded-input loop-① kernel);
            # the bytes-in entry reports the full stack it fell back from
            if tier == "hbm_slab" and name == "vocab" and carried > entry["budget"]:
                emit(
                    "KC201",
                    "error",
                    name,
                    f"hbm_slab slab block ({carried} B) exceeds the "
                    f"{entry['budget']} B slab budget",
                )
    return out


def check_shape_matrix() -> list[Finding]:
    """Sweep the routing decision space: the paper's evaluation points,
    the residency-budget boundary, and the count/slab variants."""
    from repro.core import plan as plan_lib
    from repro.core import plan_compiler

    out: list[Finding] = []
    points = [
        ("criteo-5k", schema_lib.CRITEO, {}),
        ("criteo-1m", schema_lib.CRITEO_1M, {}),
        # per-column cutoff satisfied but the 26-wide stack blows the
        # 8 MiB budget → must demote
        (
            "cutoff-width",
            dataclasses.replace(schema_lib.CRITEO, vocab_range=VMEM_TIER_MAX),
            {},
        ),
        # just inside the stack budget at 26 columns (80000·26·4 ≈ 7.9 MiB)
        (
            "budget-edge-in",
            dataclasses.replace(schema_lib.CRITEO, vocab_range=80_000),
            {},
        ),
        # just outside (81000·26·4 ≈ 8.03 MiB) while the range still
        # clears the per-column cutoff → the bytes condition alone demotes
        (
            "budget-edge-out",
            dataclasses.replace(schema_lib.CRITEO, vocab_range=81_000),
            {},
        ),
        # tracked counts double the per-entry bytes → tier tightens
        ("counts-5k", schema_lib.CRITEO, {"track_counts": True}),
        # the CI slab point: force the slab tier on a range both tiers fit
        (
            "forced-slab",
            schema_lib.CRITEO,
            {"vocab_slab_range": 1024},
        ),
    ]
    for name, schema, kw in points:
        compiled = plan_compiler.compile_plan(
            plan_lib.criteo_default(schema),
            schema,
            fused=True,
            fused_vocab=True,
            fused_decode=True,
            **kw,
        )
        out.extend(
            check_routes(compiled, max_rows=1 << 14, context=name)
        )
    return out


# --------------------------------------------------------------------- #
# AST aliasing / grid-carry audit
# --------------------------------------------------------------------- #
def _resolve_name(func: ast.FunctionDef, name: str) -> ast.expr | None:
    """Last simple ``name = <expr>`` assignment in ``func``'s body."""
    found = None
    for node in ast.walk(func):
        if isinstance(node, ast.Assign):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name) and tgt.id == name:
                    found = node.value
    return found


def _spec_list(func: ast.FunctionDef, node: ast.expr | None) -> list[ast.expr]:
    """Flatten an in_specs/out_specs expression to BlockSpec call nodes.

    Handles literal lists, a single BlockSpec call, ``[spec] * n``
    replication, name indirection (``slab_spec = pl.BlockSpec(...)``),
    and ``specs.append(name)`` augmentation."""
    if node is None:
        return []
    if isinstance(node, ast.Name):
        target = node.id
        resolved = _resolve_name(func, target)
        specs = _spec_list(func, resolved)
        # pick up list.append(...) augmentation on the same name
        for n in ast.walk(func):
            if (
                isinstance(n, ast.Call)
                and isinstance(n.func, ast.Attribute)
                and n.func.attr == "append"
                and isinstance(n.func.value, ast.Name)
                and n.func.value.id == target
            ):
                specs.extend(_spec_list(func, n.args[0]))
        return specs
    if isinstance(node, ast.List):
        out = []
        for el in node.elts:
            out.extend(_spec_list(func, el))
        return out
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Mult):
        # [spec] * len(...) — replication of one carried spec
        return _spec_list(func, node.left)
    if isinstance(node, ast.Call):
        return [node]
    return []


def _index_map_lambda(spec: ast.Call) -> ast.Lambda | None:
    for arg in spec.args:
        if isinstance(arg, ast.Lambda):
            return arg
    for kw in spec.keywords:
        if kw.arg == "index_map" and isinstance(kw.value, ast.Lambda):
            return kw.value
    return None


def _constant_dims(spec: ast.Call) -> list[int]:
    """Grid dims the spec's index map ignores — the carried dims."""
    lam = _index_map_lambda(spec)
    if lam is None:
        return []
    params = [a.arg for a in lam.args.args]
    used = {
        n.id for n in ast.walk(lam.body) if isinstance(n, ast.Name)
    }
    return [d for d, p in enumerate(params) if p not in used]


def _aliases(func: ast.FunctionDef, node: ast.expr | None) -> dict[int, int]:
    """input_output_aliases as {in_idx: out_idx}; resolves name
    indirection plus ``aliases[k] = v`` subscript augmentation."""
    if node is None:
        return {}
    out: dict[int, int] = {}
    if isinstance(node, ast.Name):
        resolved = _resolve_name(func, node.id)
        out.update(_aliases(func, resolved))
        for n in ast.walk(func):
            if (
                isinstance(n, ast.Assign)
                and isinstance(n.targets[0], ast.Subscript)
                and isinstance(n.targets[0].value, ast.Name)
                and n.targets[0].value.id == node.id
            ):
                try:
                    k = ast.literal_eval(n.targets[0].slice)
                    v = ast.literal_eval(n.value)
                    out[int(k)] = int(v)
                except (ValueError, SyntaxError):
                    pass
        return out
    if isinstance(node, ast.Dict):
        for k, v in zip(node.keys, node.values):
            try:
                out[int(ast.literal_eval(k))] = int(ast.literal_eval(v))
            except (ValueError, SyntaxError, TypeError):
                pass
    return out


def _dimension_semantics(call: ast.Call) -> list[str] | None:
    """Any declared dimension_semantics tuple under the pallas_call's
    kwargs (TPUCompilerParams(...) or a params dict)."""
    for kw in call.keywords:
        for node in ast.walk(kw.value):
            if isinstance(node, ast.keyword) and node.arg == "dimension_semantics":
                try:
                    return [str(s) for s in ast.literal_eval(node.value)]
                except (ValueError, SyntaxError):
                    return None
            if isinstance(node, ast.Dict):
                for k, v in zip(node.keys, node.values):
                    if (
                        isinstance(k, ast.Constant)
                        and k.value == "dimension_semantics"
                    ):
                        try:
                            return [str(s) for s in ast.literal_eval(v)]
                        except (ValueError, SyntaxError):
                            return None
        if kw.arg == "dimension_semantics":
            try:
                return [str(s) for s in ast.literal_eval(kw.value)]
            except (ValueError, SyntaxError):
                return None
    return None


def _kernel_fn_name(func: ast.FunctionDef, call: ast.Call) -> str | None:
    """The kernel function a pallas_call dispatches (resolves local-name
    indirection, unwraps functools.partial)."""
    if not call.args:
        return None
    fn = call.args[0]
    if isinstance(fn, ast.Name):
        resolved = _resolve_name(func, fn.id)
        if resolved is not None:  # kernel = functools.partial(_kernel, ...)
            fn = resolved
    if isinstance(fn, ast.Call) and fn.args:  # functools.partial(kernel, ...)
        fn = fn.args[0]
    if isinstance(fn, ast.Name):
        return fn.id
    if isinstance(fn, ast.Attribute):
        return fn.attr
    return None


def _has_when_init(tree: ast.Module, kernel_name: str | None) -> bool:
    if kernel_name is None:
        return False
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef) and node.name == kernel_name:
            for n in ast.walk(node):
                if (
                    isinstance(n, ast.Call)
                    and isinstance(n.func, ast.Attribute)
                    and n.func.attr == "when"
                ):
                    return True
    return False


def audit_kernel_source(
    src: str, path: str, *, root: str | None = None
) -> list[Finding]:
    """Static race/init audit of every ``pl.pallas_call`` in ``src``."""
    out: list[Finding] = []
    tree = ast.parse(src)
    rel = _rel(path, root)
    for func in [n for n in ast.walk(tree) if isinstance(n, ast.FunctionDef)]:
        for call in ast.walk(func):
            if not (
                isinstance(call, ast.Call)
                and (
                    (
                        isinstance(call.func, ast.Attribute)
                        and call.func.attr == "pallas_call"
                    )
                    or (
                        isinstance(call.func, ast.Name)
                        and call.func.id == "pallas_call"
                    )
                )
            ):
                continue
            kwargs = {k.arg: k.value for k in call.keywords if k.arg}
            out_specs = _spec_list(func, kwargs.get("out_specs"))
            aliases = _aliases(func, kwargs.get("input_output_aliases"))
            semantics = _dimension_semantics(call)
            aliased_outs = set(aliases.values())
            kernel_name = _kernel_fn_name(func, call)
            for oi, spec in enumerate(out_specs):
                carried = _constant_dims(spec)
                if not carried:
                    continue
                if oi in aliased_outs and semantics:
                    parallel = [
                        d
                        for d in carried
                        if d < len(semantics) and semantics[d] == "parallel"
                    ]
                    if parallel:
                        out.append(
                            Finding(
                                rule="KC210",
                                severity="error",
                                pass_name="kernelcheck",
                                file=rel,
                                line=call.lineno,
                                obj=f"{func.name}/out{oi}",
                                message=(
                                    f"serial-RMW accumulator (aliased "
                                    f"output {oi}) is carried across grid "
                                    f"dim(s) {parallel} declared "
                                    f'"parallel" — concurrent grid steps '
                                    "race on the block; carried dims must "
                                    "iterate sequentially"
                                ),
                            )
                        )
                if oi not in aliased_outs and not _has_when_init(
                    tree, kernel_name
                ):
                    out.append(
                        Finding(
                            rule="KC211",
                            severity="warning",
                            pass_name="kernelcheck",
                            file=rel,
                            line=call.lineno,
                            obj=f"{func.name}/out{oi}",
                            message=(
                                f"grid-carried output {oi} (index map "
                                f"constant over dim(s) {carried}) is "
                                "neither aliased from an input nor seeded "
                                "by a pl.when first-step init — its first "
                                "visit reads undefined VMEM"
                            ),
                        )
                    )
    return out


def check_repo_kernels(root: str) -> list[Finding]:
    out: list[Finding] = []
    for path in sorted(
        glob.glob(os.path.join(root, "src/repro/kernels/*/kernel.py"))
    ):
        with open(path) as f:
            src = f.read()
        out.extend(audit_kernel_source(src, path, root=root))
    return out


def run(root: str) -> list[Finding]:
    """The whole pass: shape-matrix routing agreement + kernel AST audit."""
    return check_shape_matrix() + check_repo_kernels(root)
