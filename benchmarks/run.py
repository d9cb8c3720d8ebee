"""Benchmark driver: one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows and, after every run, dumps
the same measurements machine-readably to ``BENCH_plan.json`` (section →
rows with ``us_per_call`` + parsed derived fields such as rows/s) so the
perf trajectory is diffable across commits, not just eyeballable. The
roofline section reads the dry-run artifacts when present (run ``python
-m repro.launch.dryrun --all --mesh both`` first for the full table).

    PYTHONPATH=src python -m benchmarks.run [--only fig8,table3,...]
                                           [--json-out BENCH_plan.json]
                                           [--trace trace.json]

``--trace`` additionally exports a Perfetto/chrome://tracing trace of
the whole run (with stage spans enabled, so utf8 chunks show nested
decode spans) plus a registry metrics snapshot next to it.
"""

from __future__ import annotations

import argparse
import json
import sys
import traceback

from benchmarks import (
    common,
    e2e_overlap,
    fig8_cpu_scaling,
    fig9_end2end,
    fig10_breakdown,
    fused_decode,
    fused_vocab,
    fused_xform,
    plan_bench,
    stream_service,
    table3_throughput,
    table4_operators,
)
from repro.launch import compile_cache

SECTIONS = {
    "fig8": fig8_cpu_scaling.main,
    # data-parallel ShardedPiperPipeline sweep; needs 8 host devices
    # (XLA_FLAGS=--xla_force_host_platform_device_count=8) or run it
    # standalone: python benchmarks/fig8_cpu_scaling.py --sharded
    "fig8_sharded": lambda: fig8_cpu_scaling.main(sharded=True),
    "table3": table3_throughput.main,
    "table4": table4_operators.main,
    "fig9": fig9_end2end.main,
    "fig10": fig10_breakdown.main,
    # online streaming preprocessing service: rows/s + p50/p95/p99 latency
    "stream": stream_service.main,
    # fused single-pass loop-② kernel vs unfused chain, both memory tiers
    "fused": fused_xform.main,
    # fused single-pass loop-① (GenVocab) kernel vs unfused chain; the
    # CI vocab job dumps it as BENCH_vocab.json via --json-out
    "vocab": fused_vocab.main,
    # bytes-in fused kernels (decode folded into both loops) vs the
    # decode-then-fused chains; CI decode job dumps BENCH_decode.json
    "decode": fused_decode.main,
    # compiled-plan vs legacy loop-② throughput + a crossed-feature plan
    "plan": plan_bench.main,
    # stalls-vs-overlap + chunk-cache cold/warm over real DLRM training;
    # the CI e2e job dumps it as BENCH_e2e.json via the standalone CLI
    "e2e": lambda: e2e_overlap.main(json_out=None),
}

# Sections that would perturb the others in the same process (multi-
# device XLA state; background service threads + a full training loop):
# run only when --only names them explicitly.
OPT_IN = {"fig8_sharded", "e2e"}


def main() -> None:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated section names")
    ap.add_argument(
        "--json-out",
        default="BENCH_plan.json",
        help="machine-readable dump path ('' disables)",
    )
    ap.add_argument(
        "--trace",
        default=None,
        metavar="OUT.json",
        help="export a Perfetto/chrome://tracing trace of the run, plus a "
        "metrics snapshot next to it (OUT.metrics.json)",
    )
    args = ap.parse_args()

    if args.trace:
        from repro import obs

        obs.enable()
    names = (
        args.only.split(",")
        if args.only
        else [n for n in SECTIONS if n not in OPT_IN]
    )

    print("name,us_per_call,derived")
    failures = []
    sections: dict[str, list[dict]] = {}
    for name in names:
        if name == "roofline":
            continue
        mark = len(common.RECORDS)
        try:
            SECTIONS[name]()
        except Exception as e:  # noqa: BLE001
            failures.append(name)
            traceback.print_exc()
            print(f"{name}/ERROR,0,{type(e).__name__}")
        sections[name] = common.RECORDS[mark:]

    # roofline: best-effort (requires dry-run artifacts); runs before the
    # JSON dump so its rows land in the machine-readable file too
    mark = len(common.RECORDS)
    try:
        from benchmarks import roofline

        print("\n=== §Roofline (from dry-run artifacts) ===")
        roofline.main()
        sections["roofline"] = common.RECORDS[mark:]
    except Exception:  # noqa: BLE001
        traceback.print_exc()
        print("roofline/SKIPPED (run the dry-run first)")

    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(
                {
                    "provenance": common.provenance(),
                    "sections": sections,
                    "failures": failures,
                },
                f,
                indent=2,
            )
        print(f"# wrote {args.json_out} ({sum(map(len, sections.values()))} rows)")

    if args.trace:
        from repro import obs

        obs.tracer().export(args.trace)
        mpath = args.trace.replace(".json", "") + ".metrics.json"
        obs.metrics().export_jsonl(mpath, extra={"provenance": common.provenance()})
        print(f"# wrote {args.trace} + {mpath}")

    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
