"""§Perf hillclimbing: re-lower a cell with a named variant and diff the
roofline terms against the recorded baseline.

    PYTHONPATH=src python -m benchmarks.hillclimb \
        --arch command-r-plus-104b --shape train_4k --mesh single \
        --variant sp=off --variant param_dtype=bf16 ...

Variants (comma-combinable):
    sp={on,off}            sequence-parallel residual stream
    mb=<int>               gradient-accumulation microbatches
    param_dtype={f32,bf16} parameter storage dtype (FSDP gather payload)
    cache_dtype={bf16,f8}  KV-cache dtype (decode cells)
    remat={on,off}         per-superblock rematerialization
    capf=<float>           MoE capacity factor

Each run prints the three roofline terms + memory fit, ready to paste
into EXPERIMENTS.md §Perf as hypothesis → change → before → after.
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp

from repro import configs
from repro.configs import shapes as shapes_lib
from repro.distributed import sharding as shard_lib
from repro.hw import TPU_V5E, roofline_terms
from repro.launch import hlo as hlo_lib
from repro.launch import specs as specs_lib
from repro.launch.dryrun import HBM_BYTES, _cost_dict, _lower_compile, _mem_dict
from repro.launch.mesh import make_production_mesh


def _cast_tree_dtype(sds_tree, from_dtype, to_dtype):
    def cast(s):
        if hasattr(s, "dtype") and s.dtype == from_dtype:
            return jax.ShapeDtypeStruct(s.shape, to_dtype, sharding=s.sharding)
        return s

    return jax.tree.map(cast, sds_tree)


def run_variant(arch: str, shape_name: str, mesh_kind: str, opts: dict) -> dict:
    cfg = configs.get(arch)
    if "capf" in opts and cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg,
            moe=dataclasses.replace(cfg.moe, capacity_factor=float(opts["capf"])),
        )
    shape = shapes_lib.SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))

    sp_default = (
        shape.kind == "train" and arch in specs_lib.TRAIN_SEQUENCE_PARALLEL
    )
    sp = {"on": True, "off": False}.get(opts.get("sp"), sp_default)
    mb = int(opts["mb"]) if "mb" in opts else None
    remat = opts.get("remat", "on") == "on"

    out: dict = {"variant": dict(opts), "sp": sp}
    with mesh, shard_lib.use_mesh(mesh, sequence_parallel=sp):
        # mem lowering (full config)
        cell = specs_lib.build_cell(cfg, shape, mesh, microbatches=mb, remat=remat)
        if opts.get("param_dtype") == "bf16":
            cell = dataclasses.replace(
                cell,
                args=(_cast_tree_dtype(cell.args[0], jnp.float32, jnp.bfloat16),)
                + cell.args[1:],
            )
        if opts.get("cache_dtype") == "f8" and cell.kind == "decode":
            cell = dataclasses.replace(
                cell,
                args=(cell.args[0], _cast_tree_dtype(cell.args[1], jnp.bfloat16, jnp.float8_e4m3fn))
                + cell.args[2:],
            )
        compiled, times = _lower_compile(cell, donate=cell.kind == "train")
        mem = _mem_dict(compiled)
        used = (
            mem["argument_bytes"] + mem["temp_bytes"] + mem["output_bytes"]
            - mem["alias_bytes"]
        )
        out["mem_gib"] = used / 2**30
        out["fits_hbm"] = used <= HBM_BYTES
        out["compile_s"] = times["compile_s"]

        # cost lowerings (depth 1/2, unrolled)
        cost = {}
        for depth in (1, 2):
            ccfg = cfg
            if ccfg.ssm is not None:
                ccfg = dataclasses.replace(
                    ccfg, ssm=dataclasses.replace(ccfg.ssm, chunk=shape.seq_len)
                )
            cell_c = specs_lib.build_cell(
                ccfg, shape, mesh,
                microbatches=1,
                attn_block_k=shape.seq_len,
                ce_block=shape.seq_len,
                unroll=True,
                n_superblocks_override=depth,
            )
            if opts.get("param_dtype") == "bf16":
                cell_c = dataclasses.replace(
                    cell_c,
                    args=(_cast_tree_dtype(cell_c.args[0], jnp.float32, jnp.bfloat16),)
                    + cell_c.args[1:],
                )
            if opts.get("cache_dtype") == "f8" and cell_c.kind == "decode":
                cell_c = dataclasses.replace(
                    cell_c,
                    args=(cell_c.args[0], _cast_tree_dtype(cell_c.args[1], jnp.bfloat16, jnp.float8_e4m3fn))
                    + cell_c.args[2:],
                )
            compiled_c, _ = _lower_compile(cell_c, donate=False)
            cost[depth] = {
                **_cost_dict(compiled_c),
                "coll": hlo_lib.collective_stats(compiled_c.as_text()),
            }
        n_sb = cfg.n_superblocks
        df = cost[2]["flops"] - cost[1]["flops"]
        db = cost[2]["bytes"] - cost[1]["bytes"]
        flops = (cost[1]["flops"] - df) + n_sb * df
        bytes_ = (cost[1]["bytes"] - db) + n_sb * db
        c1, c2 = cost[1]["coll"]["bytes_by_op"], cost[2]["coll"]["bytes_by_op"]
        coll_by = {}
        for op in set(c1) | set(c2):
            d = c2.get(op, 0.0) - c1.get(op, 0.0)
            coll_by[op] = (c1.get(op, 0.0) - d) + n_sb * d
        coll = float(sum(coll_by.values()))
        out["flops"] = flops
        out["bytes"] = bytes_
        out["collective_bytes"] = coll
        out["collective_by_op"] = coll_by
        out["terms_ms"] = {
            k: v * 1e3 for k, v in roofline_terms(flops, bytes_, coll, 1, TPU_V5E).items()
        }
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--mesh", default="single")
    ap.add_argument(
        "--variant", action="append", default=[], help="key=value (repeatable)"
    )
    args = ap.parse_args()
    opts = dict(v.split("=", 1) for v in args.variant)
    t0 = time.time()
    out = run_variant(args.arch, args.shape, args.mesh, opts)
    out["wall_s"] = round(time.time() - t0, 1)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
