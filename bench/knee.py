"""Sweep the open-loop rate of a serving cell to find its knee.

    python bench/knee.py --workload <serving cell> --seed <n> --seconds 20 --rates 40,60,80

One process sets the cell up once and runs a window at each rate in
turn (the cell's own ``rate_per_s`` is ignored). For each rate it prints
the requests offered and answered, rows/s answered, the latency median
and 99th percentile, and whether the backlog grew: the requests still
open when the last one was due, and the latency median of the last third
of the window against the first third. The knee is the highest rate at
which the backlog does not grow. Needs a TPU.
"""

import argparse
import json
import os
import pathlib
import sys

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT / "src"), str(HERE), str(HERE / "traffic")]
    import harness
    import open_loop

    cat = harness.Catalog()
    cell = cat.cell(args.workload)
    job = open_loop.setup({"cell": cell, "seed": args.seed, "devices": harness.tpu_devices(cell["chips"])})
    try:
        for rate in [float(r) for r in args.rates.split(",")]:
            s = open_loop.serve(job, rate, args.seconds)
            lat = (s["done"] - s["due"]) * 1e3
            ok = ~np.isnan(lat)
            rel = s["due"] - s["due"][0]
            first, last = rel < args.seconds / 3, rel >= 2 * args.seconds / 3
            open_at_end = int(np.sum(~(s["done"] <= s["due"][-1])))
            span = np.nanmax(s["done"]) - s["due"][0]
            print(json.dumps({
                "rate_per_s": rate,
                "requests": len(lat),
                "answered": int(ok.sum()),
                "rows_per_s": float(s["sizes"][ok].sum() / span),
                "p50_ms": float(np.nanpercentile(lat, 50)),
                "p99_ms": float(np.nanpercentile(lat, 99)),
                "p50_first_third_ms": float(np.nanpercentile(lat[first], 50)),
                "p50_last_third_ms": float(np.nanpercentile(lat[last], 50)),
                "open_at_last_due": open_at_end,
                "generator_late_p99_ms": float(np.percentile(s["late"], 99) * 1e3),
            }), flush=True)
    finally:
        job.svc.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
