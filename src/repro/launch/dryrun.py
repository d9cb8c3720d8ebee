import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

THE two lines above must run before any other import (jax locks the
device count at first init) — do not move them.

For each cell this driver produces three lowerings:

  1. **mem** — the full, real configuration (true depth, microbatches,
     block_k=1024 chunked attention, remat). ``compiled.memory_analysis()``
     proves the cell fits 16 GB/chip; the compiled HLO records the
     collective schedule. This is the pass/fail deliverable.
  2. **cost@1 / cost@2** — the same cell at n_superblocks ∈ {1, 2} with
     microbatches=1 and single-block attention (inner scans have trip
     count 1). XLA's cost analysis counts ``while`` bodies ONCE, so
     full-depth totals are reconstructed as
         total = fixed + n_superblocks × (cost@2 − cost@1)
     for FLOPs, bytes, and per-op collective bytes alike. (benchmarks/
     roofline.py consumes these numbers and applies the documented
     kernel adjustments.)

Results are cached as JSON under experiments/dryrun/ — one file per
cell — and are idempotent (--force to re-run).

Usage:
    python -m repro.launch.dryrun --arch gemma-2b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all --mesh both
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax

from repro import configs
from repro.configs import shapes as shapes_lib
from repro.distributed import sharding as shard_lib
from repro.launch import compile_cache
from repro.launch import hlo as hlo_lib
from repro.launch import specs as specs_lib
from repro.launch.mesh import make_production_mesh

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "experiments", "dryrun")
HBM_BYTES = 16 * 1024**3


def _cost_dict(compiled) -> dict:
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
    }


def _mem_dict(compiled) -> dict:
    ma = compiled.memory_analysis()
    return {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "code_bytes": int(ma.generated_code_size_in_bytes),
    }


def _lower_compile(cell: specs_lib.Cell, donate: bool):
    jitted = jax.jit(
        cell.step_fn,
        out_shardings=cell.out_shardings,
        donate_argnums=(0, 1) if donate else (),
    )
    t0 = time.time()
    lowered = jitted.lower(*cell.args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    return compiled, {"lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2)}


def run_cell(arch: str, shape_name: str, mesh_kind: str, seq_override: int | None = None) -> dict:
    cfg = configs.get(arch)
    shape = shapes_lib.SHAPES[shape_name]
    ok, reason = shapes_lib.applicable(cfg, shape)
    if not ok:
        return {
            "status": "skip",
            "reason": reason,
            "arch": arch,
            "shape": shape_name,
            "mesh": mesh_kind,
        }

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    record: dict = {
        "status": "ok",
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_kind,
        "mesh_shape": dict(zip(mesh.axis_names, [mesh.shape[a] for a in mesh.axis_names])),
        "n_devices": mesh.size,
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "n_superblocks": cfg.n_superblocks,
        "superblock_len": len(cfg.superblock),
    }

    seq_parallel = (
        shape.kind == "train" and arch in specs_lib.TRAIN_SEQUENCE_PARALLEL
    )
    record["sequence_parallel"] = seq_parallel
    with mesh, shard_lib.use_mesh(mesh, sequence_parallel=seq_parallel):
        # --- 1. mem lowering: the real thing -------------------------- #
        cell = specs_lib.build_cell(cfg, shape, mesh)
        compiled, times = _lower_compile(cell, donate=cell.kind == "train")
        mem = _mem_dict(compiled)
        mem["fits_hbm"] = (
            mem["argument_bytes"] + mem["temp_bytes"] + mem["output_bytes"]
            - mem["alias_bytes"]
        ) <= HBM_BYTES
        record["mem"] = mem
        record["mem_times"] = times
        record["mem_cost_raw"] = _cost_dict(compiled)  # body-once counting
        record["mem_collectives_raw"] = hlo_lib.collective_stats(compiled.as_text())
        record["microbatches"] = cell.meta.get("microbatches", 1)

        # --- 2. cost lowerings at depth 1 and 2 ----------------------- #
        cost = {}
        for depth in (1, 2):
            ccfg = cfg
            if ccfg.ssm is not None:
                ccfg = dataclasses.replace(
                    ccfg,
                    ssm=dataclasses.replace(ccfg.ssm, chunk=shape.seq_len),
                )
            cell_c = specs_lib.build_cell(
                ccfg,
                shape,
                mesh,
                microbatches=1,
                attn_block_k=shape.seq_len,
                ce_block=shape.seq_len,
                unroll=True,
                n_superblocks_override=depth,
            )
            compiled_c, times_c = _lower_compile(cell_c, donate=False)
            cost[depth] = {
                **_cost_dict(compiled_c),
                "collectives": hlo_lib.collective_stats(compiled_c.as_text()),
                "times": times_c,
            }
        n_sb = cfg.n_superblocks
        d_flops = cost[2]["flops"] - cost[1]["flops"]
        d_bytes = cost[2]["bytes"] - cost[1]["bytes"]
        coll1 = cost[1]["collectives"]["bytes_by_op"]
        coll2 = cost[2]["collectives"]["bytes_by_op"]
        ops = set(coll1) | set(coll2)
        coll_total = {}
        for op in ops:
            d = coll2.get(op, 0.0) - coll1.get(op, 0.0)
            coll_total[op] = (coll1.get(op, 0.0) - d) + n_sb * d
        record["cost_extrapolated"] = {
            "flops": (cost[1]["flops"] - d_flops) + n_sb * d_flops,
            "bytes": (cost[1]["bytes"] - d_bytes) + n_sb * d_bytes,
            "collective_bytes_by_op": coll_total,
            "collective_bytes": float(sum(coll_total.values())),
            "per_superblock": {"flops": d_flops, "bytes": d_bytes},
        }
        record["cost_raw"] = {str(k): v for k, v in cost.items()}
    return record


def run_cell_piper(vocab_range: int, mesh_kind: str) -> dict:
    """Dry-run the paper's own technique: the column-parallel PIPER
    preprocessing engine on the production mesh.

    mem lowering: the full two-loop ``run_scan``; cost lowerings: the
    per-chunk ``vocab_step`` / ``transform_step`` plus ``finalize`` (the
    epoch's single collective), reported separately — the streaming loop
    repeats the chunk steps, so per-chunk numbers are the roofline unit.
    """
    import dataclasses as dc

    import jax.numpy as jnp

    from repro.core import pipeline as pipeline_lib
    from repro.core import schema as schema_lib
    from repro.core import sharded as sharded_lib

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    schema = dc.replace(schema_lib.CRITEO, vocab_range=vocab_range)
    chunk_bytes = 1 << 20
    pc = pipeline_lib.PipelineConfig(
        schema=schema, chunk_bytes=chunk_bytes, max_rows_per_chunk=1 << 13
    )
    eng = sharded_lib.ShardedPiper(pc, mesh)
    record: dict = {
        "status": "ok",
        "arch": f"piper-preprocess-{vocab_range//1000}k",
        "shape": "stream_1mb",
        "mesh": mesh_kind,
        "mesh_shape": dict(
            zip(mesh.axis_names, [mesh.shape[a] for a in mesh.axis_names])
        ),
        "n_devices": mesh.size,
        "vocab_range": vocab_range,
        "chunk_bytes": chunk_bytes,
        "row_shards": eng.n_row_shards,
    }
    from jax.sharding import NamedSharding, PartitionSpec as P

    d = eng.n_row_shards
    row_axes = eng.row_axes
    chunks_sds = jax.ShapeDtypeStruct(
        (d, chunk_bytes), jnp.uint8, sharding=NamedSharding(mesh, P(row_axes, None))
    )
    offs_sds = jax.ShapeDtypeStruct(
        (d,), jnp.int32, sharding=NamedSharding(mesh, P(row_axes))
    )
    state_shape = jax.eval_shape(eng.init_state)
    state_sds = jax.ShapeDtypeStruct(
        state_shape.shape, state_shape.dtype, sharding=eng.state_sharding()
    )

    with mesh, shard_lib.use_mesh(mesh):
        # mem: full two-loop scan over 2 steps
        stacked = jax.ShapeDtypeStruct((2, d, chunk_bytes), jnp.uint8)
        offs2 = jax.ShapeDtypeStruct((2, d), jnp.int32)
        t0 = time.time()
        compiled = jax.jit(eng.run_scan).lower(stacked, offs2).compile()
        record["mem"] = _mem_dict(compiled)
        record["mem"]["fits_hbm"] = (
            record["mem"]["argument_bytes"]
            + record["mem"]["temp_bytes"]
            + record["mem"]["output_bytes"]
            - record["mem"]["alias_bytes"]
        ) <= HBM_BYTES
        record["mem_times"] = {"compile_s": round(time.time() - t0, 2)}

        cost = {}
        for name, fn, args in (
            ("vocab_step", eng.vocab_step, (state_sds, chunks_sds, offs_sds)),
            ("finalize", lambda s: eng.finalize(s).table, (state_sds,)),
        ):
            c = jax.jit(fn).lower(*args).compile()
            cost[name] = {
                **_cost_dict(c),
                "collectives": hlo_lib.collective_stats(c.as_text()),
            }
        # transform_step needs a Vocabulary skeleton (table model-sharded)
        vocab_shape = jax.eval_shape(lambda s: eng.finalize(s), state_sds)
        from repro.core import vocab as vocab_lib

        vocab_skel = vocab_lib.Vocabulary(
            table=jax.ShapeDtypeStruct(
                vocab_shape.table.shape,
                vocab_shape.table.dtype,
                sharding=NamedSharding(mesh, P("model", None)),
            ),
            sizes=jax.ShapeDtypeStruct(
                vocab_shape.sizes.shape,
                vocab_shape.sizes.dtype,
                sharding=NamedSharding(mesh, P("model")),
            ),
        )
        c = jax.jit(eng.transform_step).lower(vocab_skel, chunks_sds).compile()
        cost["transform_step"] = {
            **_cost_dict(c),
            "collectives": hlo_lib.collective_stats(c.as_text()),
        }
        record["cost_stages"] = cost
        per_chunk = {
            "flops": cost["vocab_step"]["flops"] + cost["transform_step"]["flops"],
            "bytes": cost["vocab_step"]["bytes"] + cost["transform_step"]["bytes"],
            "collective_bytes": (
                cost["vocab_step"]["collectives"]["total_bytes"]
                + cost["transform_step"]["collectives"]["total_bytes"]
            ),
        }
        record["cost_per_chunk"] = per_chunk
    return record


def cell_path(arch: str, shape_name: str, mesh_kind: str, out_dir: str) -> str:
    return os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_kind}.json")


def main() -> None:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=os.path.abspath(OUT_DIR))
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)

    # the paper's own technique as extra cells: --arch piper (or --all)
    if args.arch == "piper" or args.all:
        meshes_pp = ["single", "multi"] if args.mesh == "both" else [args.mesh]
        for vocab_range in (5_000, 1_000_000):
            for mesh_kind in meshes_pp:
                tag = f"piper-preprocess-{vocab_range//1000}k"
                path = cell_path(tag, "stream_1mb", mesh_kind, args.out)
                if os.path.exists(path) and not args.force:
                    print(f"[cached] {tag} {mesh_kind}")
                    continue
                t0 = time.time()
                try:
                    record = run_cell_piper(vocab_range, mesh_kind)
                except Exception as e:  # noqa: BLE001
                    record = {
                        "status": "error",
                        "arch": tag,
                        "error": f"{type(e).__name__}: {e}",
                        "traceback": traceback.format_exc()[-4000:],
                    }
                record["wall_s"] = round(time.time() - t0, 1)
                with open(path, "w") as f:
                    json.dump(record, f, indent=1)
                print(f"[{record['status']:5s}] {tag:28s} {mesh_kind:6s} ({record['wall_s']}s)")
        if args.arch == "piper":
            return

    archs = list(configs.ARCH_IDS) if (args.all or args.arch is None) else [args.arch]
    shapes = (
        [s.name for s in shapes_lib.ALL_SHAPES]
        if (args.all or args.shape is None)
        else [args.shape]
    )
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    n_ok = n_skip = n_err = 0
    for arch in archs:
        for shape_name in shapes:
            for mesh_kind in meshes:
                path = cell_path(arch, shape_name, mesh_kind, args.out)
                if os.path.exists(path) and not args.force:
                    print(f"[cached] {arch} {shape_name} {mesh_kind}")
                    continue
                t0 = time.time()
                try:
                    record = run_cell(arch, shape_name, mesh_kind)
                except Exception as e:  # noqa: BLE001 — record and continue
                    record = {
                        "status": "error",
                        "arch": arch,
                        "shape": shape_name,
                        "mesh": mesh_kind,
                        "error": f"{type(e).__name__}: {e}",
                        "traceback": traceback.format_exc()[-4000:],
                    }
                record["wall_s"] = round(time.time() - t0, 1)
                with open(path, "w") as f:
                    json.dump(record, f, indent=1)
                status = record["status"]
                n_ok += status == "ok"
                n_skip += status == "skip"
                n_err += status == "error"
                extra = ""
                if status == "ok":
                    mem = record["mem"]
                    used = (
                        mem["argument_bytes"] + mem["temp_bytes"]
                        + mem["output_bytes"] - mem["alias_bytes"]
                    )
                    extra = (
                        f"mem/dev={used/2**30:.2f}GiB fits={mem['fits_hbm']} "
                        f"flops={record['cost_extrapolated']['flops']:.3g} "
                        f"coll={record['cost_extrapolated']['collective_bytes']:.3g}B"
                    )
                elif status == "skip":
                    extra = record["reason"][:60]
                else:
                    extra = record["error"][:120]
                print(
                    f"[{status:5s}] {arch:22s} {shape_name:12s} {mesh_kind:6s} "
                    f"({record['wall_s']:6.1f}s) {extra}"
                )
    print(f"dry-run complete: {n_ok} ok, {n_skip} skip, {n_err} error")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
