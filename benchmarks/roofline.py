"""§Roofline: per-(arch × shape × mesh) three-term analysis.

Reads the dry-run JSONs (experiments/dryrun/) and derives, per cell:

    compute_s    = HLO_FLOPs / peak_bf16            (per-device values)
    memory_s     = HLO_bytes / HBM_bw
    collective_s = collective_bytes / ICI_link_bw

plus the documented **kernel adjustments** that map the XLA-lowered cost
model onto the Pallas-kernel execution the TPU target actually runs:

  A1 causal-skip (compute): the cost lowering masks-but-computes the
     upper triangle of causal self-attention; the flash kernel skips
     those blocks → subtract ½ of the analytic attention matmul FLOPs.
  A2 VMEM scores (memory): the lowered graph materializes f32 score
     blocks to HBM; the flash kernel keeps them in VMEM → subtract the
     analytic score-tensor traffic.
  A3 sLSTM recurrence (compute, xlstm only): the sequential time scan is
     counted once by XLA's cost model → add (T-1)·body FLOPs.

Both raw and adjusted terms are reported; the bottleneck verdict uses
the adjusted ones. MODEL_FLOPS = 6·N_active·tokens (train) or
2·N_active·tokens (prefill/decode); usefulness = MODEL_FLOPS/HLO_FLOPs.
"""

from __future__ import annotations

import glob
import json
import os

from repro import configs
from repro.configs import shapes as shapes_lib
from repro.hw import TPU_V5E, roofline_terms
from repro.models.common import ModelConfig

DRYRUN_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments", "dryrun")


def _mesh_sizes(record):
    ms = record["mesh_shape"]
    dp = ms.get("pod", 1) * ms.get("data", 1)
    return dp, ms.get("model", 1), record["n_devices"]


def _attn_geometry(cfg: ModelConfig, shape, dp: int, tp: int):
    """Per-device analytic attention matmul FLOPs + score bytes (fwd)."""
    b_loc = shape.global_batch / dp if shape.global_batch % dp == 0 else shape.global_batch
    hq_loc = cfg.n_heads / tp if cfg.n_heads % tp == 0 else cfg.n_heads
    n_self = sum(
        1 for s in cfg.superblock if s.kind in ("attn", "hymba") and s.attn != "cross"
    ) * cfg.n_superblocks
    n_causal = n_self  # all self-attn layers here are causal except whisper enc
    sq = skv = shape.seq_len
    # sliding-window layers attend to ≤ window keys
    flops = 0.0
    score_bytes = 0.0
    for s in cfg.superblock:
        if s.kind not in ("attn", "hymba") or s.attn == "cross":
            continue
        eff_kv = min(s.window, skv) if s.window else skv
        f = 4 * b_loc * hq_loc * sq * eff_kv * cfg.head_dim
        flops += f * cfg.n_superblocks
        score_bytes += 4 * b_loc * hq_loc * sq * eff_kv * cfg.n_superblocks
    if cfg.n_encoder_superblocks:
        f_enc = shape.global_batch / dp if shape.global_batch % dp == 0 else shape.global_batch
        fenc = 4 * f_enc * hq_loc * cfg.encoder_frames ** 2 * cfg.head_dim
        flops += fenc * cfg.n_encoder_superblocks
        score_bytes += 4 * f_enc * hq_loc * cfg.encoder_frames ** 2 * cfg.n_encoder_superblocks
    return flops, score_bytes, n_causal


def _slstm_adjustment(cfg: ModelConfig, shape, dp: int) -> float:
    n_slstm = sum(1 for s in cfg.superblock if s.kind == "slstm") * cfg.n_superblocks
    if not n_slstm or shape.kind == "decode":
        return 0.0
    b_loc = shape.global_batch / dp if shape.global_batch % dp == 0 else shape.global_batch
    d = cfg.d_model
    dh = d // cfg.n_heads
    body = 2 * b_loc * d * 4 * dh  # recurrent einsum per step (fwd)
    return n_slstm * (shape.seq_len - 1) * body


def analyze_cell(record: dict) -> dict | None:
    if record["status"] != "ok" or "cost_extrapolated" not in record:
        return None  # piper-preprocess cells are reported separately
    cfg = configs.get(record["arch"])
    shape = shapes_lib.SHAPES[record["shape"]]
    dp, tp, n_dev = _mesh_sizes(record)

    flops = record["cost_extrapolated"]["flops"]
    bytes_ = record["cost_extrapolated"]["bytes"]
    coll = record["cost_extrapolated"]["collective_bytes"]
    coll_by_op = record["cost_extrapolated"]["collective_bytes_by_op"]

    # --- adjustments -------------------------------------------------- #
    passes = {"train": 3.0, "prefill": 1.0, "decode": 1.0}[shape.kind]
    mem_passes = {"train": 2.0, "prefill": 1.0, "decode": 0.0}[shape.kind]
    adj_flops = flops
    adj_bytes = bytes_
    if shape.kind in ("train", "prefill"):
        attn_flops, score_bytes, _ = _attn_geometry(cfg, shape, dp, tp)
        adj_flops = flops - 0.5 * attn_flops * passes          # A1
        adj_bytes = bytes_ - 2 * score_bytes * mem_passes      # A2
    adj_flops += _slstm_adjustment(cfg, shape, dp) * passes     # A3
    # clamp: when the analytic adjustment would erase >60% of the
    # measured number, the sharded geometry diverged from the analytic
    # model (e.g. replicated MQA heads) — cap rather than extrapolate
    adj_flops = max(adj_flops, 0.4 * flops)
    adj_bytes = max(adj_bytes, 0.4 * bytes_)

    raw = roofline_terms(flops, bytes_, coll, n_chips=1, chip=TPU_V5E)
    adj = roofline_terms(adj_flops, adj_bytes, coll, n_chips=1, chip=TPU_V5E)
    dominant = max(adj, key=adj.get)

    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mf = (6 if shape.kind == "train" else 2) * record["active_params"] * tokens
    mf_per_dev = mf / n_dev
    useful = mf_per_dev / max(adj_flops, 1.0)

    hints = {
        "compute_s": "raise MXU utilization: bigger per-device microbatch, "
        "fused flash blocks, fewer remat recomputes",
        "memory_s": "cut HBM traffic: bf16 cache/activations, int8 KV cache, "
        "larger attention blocks (fewer KV re-reads), fuse elementwise chains",
        "collective_s": "re-shard to remove the top collective "
        f"({max(coll_by_op, key=coll_by_op.get) if coll_by_op else 'none'}); "
        "overlap via async collectives / communication-compute fusion",
    }
    return {
        "arch": record["arch"],
        "shape": record["shape"],
        "mesh": record["mesh"],
        "raw": raw,
        "adj": adj,
        "dominant": dominant,
        "collective_by_op": coll_by_op,
        "model_flops_per_dev": mf_per_dev,
        "useful_ratio": useful,
        "fits_hbm": record["mem"]["fits_hbm"],
        "mem_gib": (
            record["mem"]["argument_bytes"]
            + record["mem"]["temp_bytes"]
            + record["mem"]["output_bytes"]
            - record["mem"]["alias_bytes"]
        )
        / 2**30,
        "hint": hints[dominant],
    }


def main() -> None:
    out_dir = os.path.abspath(DRYRUN_DIR)
    rows = []
    skips = []
    piper_rows = []
    for path in sorted(glob.glob(os.path.join(out_dir, "*.json"))):
        record = json.load(open(path))
        if record["status"] == "skip":
            skips.append((record.get("arch"), record.get("shape"), record.get("mesh")))
            continue
        if record["status"] == "ok" and "cost_per_chunk" in record:
            piper_rows.append(record)
            continue
        try:
            cell = analyze_cell(record)
        except Exception:  # noqa: BLE001 — malformed/legacy record
            cell = None
        if cell:
            rows.append(cell)

    hdr = (
        f"{'arch':22s} {'shape':12s} {'mesh':6s} "
        f"{'comp(ms)':>9s} {'mem(ms)':>9s} {'coll(ms)':>9s} "
        f"{'dominant':>12s} {'useful':>7s} {'fits':>5s}"
    )
    print(hdr)
    print("-" * len(hdr))
    for r in rows:
        print(
            f"{r['arch']:22s} {r['shape']:12s} {r['mesh']:6s} "
            f"{r['adj']['compute_s']*1e3:9.2f} {r['adj']['memory_s']*1e3:9.2f} "
            f"{r['adj']['collective_s']*1e3:9.2f} "
            f"{r['dominant'].replace('_s',''):>12s} {r['useful_ratio']:7.3f} "
            f"{str(bool(r['fits_hbm'])):>5s}"
        )
    for arch, shape, mesh in skips:
        print(
            f"{arch or '?':22s} {shape or 'long_500k':12s} {mesh or '?':6s} "
            f" -- skipped (per DESIGN.md §Arch-applicability)"
        )

    if piper_rows:
        print("\n-- the paper's technique: PIPER preprocessing engine --")
        for r in piper_rows:
            pc = r["cost_per_chunk"]
            t = roofline_terms(
                pc["flops"], pc["bytes"], pc["collective_bytes"], 1, TPU_V5E
            )
            fin = r["cost_stages"]["finalize"]["collectives"]["total_bytes"]
            dom = max(t, key=t.get)
            print(
                f"{r['arch']:22s} {r['shape']:12s} {r['mesh']:6s} "
                f"{t['compute_s']*1e3:9.3f} {t['memory_s']*1e3:9.3f} "
                f"{t['collective_s']*1e3:9.3f} {dom.replace('_s',''):>12s} "
                f"| steady-state collectives: {pc['collective_bytes']:.0f} B; "
                f"finalize all-reduce: {fin:.3g} B/dev/epoch"
            )

    with open(os.path.join(out_dir, "..", "roofline.json"), "w") as f:
        json.dump(rows, f, indent=1)
    print(f"\n{len(rows)} cells analyzed → experiments/roofline.json")


if __name__ == "__main__":
    main()
