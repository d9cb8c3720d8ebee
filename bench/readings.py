"""Readings behind the limits of ``correct``: many seeds in one process.

    python bench/readings.py --workload <cell> --seeds 1,2,3 --seconds 5

For each seed it sets the cell up, runs its window (the timed path, at
the cell's sizes), compares with the reference and prints each number
compared, and the control's reading of the dense number: the reference
computed in bfloat16 on the device, in the program's place, over the
same rows. ``PERF.md`` sets each limit from these readings. Needs a TPU.
"""

import argparse
import json
import os
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT / "src"), str(HERE)]
    import harness
    import reference

    cat = harness.Catalog()
    cell = cat.cell(args.workload)
    kind = cat.kind(cell["kind"])
    devices = harness.tpu_devices(cell["chips"])
    for seed in [int(s) for s in args.seeds.split(",")]:
        job = kind.setup({"cell": cell, "seed": seed, "devices": devices})
        res = kind.run(job, args.seconds, False)
        checks = reference.Checks()
        kind.check(job, checks)
        dense = job.table["dense"]
        control = reference.dense_rel_err(reference.dense_control(dense), reference.dense_reference(dense))
        print(json.dumps({
            "seed": seed,
            "correct": checks.correct,
            "attempted": res["attempted"],
            "failed": res["failed"],
            "checks": {k: v["value"] for k, v in checks.as_dict().items()},
            "control_dense_max_rel_err": control,
        }), flush=True)
        del job
    return 0


if __name__ == "__main__":
    sys.exit(main())
