"""Record a small device trace of one offline job, for the trace tests.

    python bench/record_trace.py --out <file.json.gz> [--config criteo-kaggle-5k] [--chunks 2]

Runs one warm job of the ``offline_job`` kind on the chip under the
profiler and writes the normalized trace (``devtrace.load_xplane``'s
form) with the job's needed bytes and chunk count. It also prints each
profiler plane and line with its event count and the programs seen, so
that a change in the profiler's layout shows. Needs a TPU.
"""

import argparse
import gzip
import json
import os
import pathlib
import sys
import time

T_START = time.perf_counter()
HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--config", default="criteo-kaggle-5k")
    ap.add_argument("--chunks", type=int, default=2)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT / "src"), str(HERE), str(HERE / "traffic")]
    import glob
    import shutil
    import tempfile

    from jax import profiler

    import devtrace
    import harness
    import needed_bytes
    import offline_job

    devices = harness.tpu_devices(1)
    cat = harness.Catalog()
    cell = {"config": cat.config(args.config), "params": {"chunks": args.chunks}, "chips": 1}
    job = offline_job.setup({"cell": cell, "seed": args.seed, "devices": devices})
    job.run_once()
    d = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        opts = profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        profiler.start_trace(d, profiler_options=opts)
        with profiler.TraceAnnotation("bench/window"):
            job.run_once()
        profiler.stop_trace()
        path = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
        data = profiler.ProfileData.from_file(path)
        for plane in data.planes:
            print("plane", repr(plane.name))
            for line in plane.lines:
                names = sorted({e.name for e in line.events})
                print("  line", repr(line.name), len(list(line.events)), names[:12])
        trace = devtrace.load_xplane(path)
    finally:
        shutil.rmtree(d, ignore_errors=True)
    window = devtrace.window_of(trace, "bench/window")
    doc = {
        "config": args.config,
        "chunks": args.chunks,
        "device_kind": devices[0].device_kind,
        "needed_bytes": needed_bytes.job_bytes(
            job.table, job.rows_per_chunk, job.bytes_per_chunk, job.cfg["vocab_range"]
        ),
        "programs": devtrace.program_table(trace, window),
        "trace": trace,
    }
    with gzip.open(args.out, "wt") as f:
        json.dump(doc, f)
    print(json.dumps({k: doc[k] for k in ("config", "chunks", "device_kind", "needed_bytes", "programs")}))
    print(json.dumps(devtrace.summary(trace, window)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
