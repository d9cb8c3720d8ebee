"""The benchmark harness: finds every part by name and runs one cell once.

``BENCHMARK.json`` names the cells; each part of a cell is a file of its
own under ``bench/``, found by that name:

  configs/<config>.json     the deployment: schema, sizes, source, cuts
  traffic/<traffic>.json    a traffic mix: ``{"kind": ..., "params": {...}}``
  traffic/<kind>.py         the generator and driver of one kind of traffic
  cells/<workload>.json     the cell: its config, traffic and parameters
  metrics/<metric>.py       the reader of one per-layer metric

A traffic kind module defines ``setup(ctx) -> job``, ``run(job,
seconds, trace) -> dict`` (the timed window) and ``check(job, checks)``
(the comparison with the reference, after the window). A metric reader
defines ``read(ctx) -> float | None``; ``None`` leaves the metric out.
Adding a cell, a mix or a metric adds files and edits none.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import sys
import threading
import time

import reference

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, bad catalog)."""


# ---------------------------------------------------------------------- #
# catalog
# ---------------------------------------------------------------------- #
def _load_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise BenchError(f"missing {path}")
    with open(path) as f:
        return json.load(f)


def _load_module(path: pathlib.Path, name: str):
    if not path.is_file():
        raise BenchError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Catalog:
    """``BENCHMARK.json`` at ``root`` and the files it names under
    ``root/bench``; traffic kinds come from this harness's directory."""

    def __init__(self, root: pathlib.Path = ROOT):
        self.root = pathlib.Path(root)
        self.dir = self.root / "bench"
        self.spec = _load_json(self.root / "BENCHMARK.json")

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        return _load_json(self.dir / "configs" / f"{name}.json")

    def cell(self, workload: str) -> dict:
        """The cell's config and traffic names and its parameters (the
        traffic mix's, updated by the cell's own)."""
        w = self.workload(workload)
        cell = _load_json(self.dir / "cells" / f"{workload}.json")
        if (cell["config"], cell["traffic"]) != (w["config"], w["traffic"]):
            raise BenchError(f"cells/{workload}.json disagrees with BENCHMARK.json")
        traffic = _load_json(self.dir / "traffic" / f"{w['traffic']}.json")
        params = dict(traffic["params"])
        params.update(cell.get("params", {}))
        return {
            "name": workload,
            "chips": int(w["chips"]),
            "config": self.config(w["config"]),
            "kind": traffic["kind"],
            "params": params,
        }

    def kind(self, kind: str):
        if not (HERE / "traffic" / f"{kind}.py").is_file():
            raise BenchError(f"no traffic kind {kind!r} in {HERE / 'traffic'}")
        if str(HERE / "traffic") not in sys.path:
            sys.path.insert(0, str(HERE / "traffic"))
        return importlib.import_module(kind)

    def reader(self, metric: str):
        return _load_module(self.dir / "metrics" / f"{metric}.py", f"bench_metric_{metric}")

    def _applies(self, metric: dict, workload: str, reported: set) -> bool:
        if "workloads" in metric:
            return workload in metric["workloads"]
        return metric.get("moves", metric["name"]) in reported

    def end_to_end(self, workload: str) -> list[dict]:
        names = {m["name"] for m in self.spec["end_to_end"]}
        return [m for m in self.spec["end_to_end"] if self._applies(m, workload, names)]

    def per_layer(self, workload: str) -> list[dict]:
        reported = {m["name"] for m in self.end_to_end(workload)}
        return [m for m in self.spec["per_layer"] if self._applies(m, workload, reported)]


# ---------------------------------------------------------------------- #
# device
# ---------------------------------------------------------------------- #
def tpu_devices(chips: int):
    """The first ``chips`` TPU devices; raises without a TPU or with too
    few chips (no run ever falls back to the CPU)."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"needs a TPU, JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise BenchError(f"cell needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def device_record(devices) -> dict:
    import jax

    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": peak,
    }


class CompileClock:
    """Lowerings and compilations JAX reports through ``jax.monitoring``
    (copied from ``chip_smoke.py``): seconds, and a count of events."""

    EVENTS = (
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, duration, **_):
        if event in self.EVENTS:
            with self._lock:
                self.seconds += duration
                self.count += 1

    def read(self) -> tuple[float, int]:
        with self._lock:
            return self.seconds, self.count


def log(**fields) -> None:
    print(json.dumps(fields), file=sys.stderr, flush=True)


# ---------------------------------------------------------------------- #
# one run
# ---------------------------------------------------------------------- #
def run_cell(
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    t_start: float,
    catalog: Catalog | None = None,
    devices=None,
) -> dict:
    """Run one cell once and return the result line (a dict).

    ``devices`` defaults to :func:`tpu_devices`; tests pass CPU devices
    here to drive the rest of a run without a chip."""
    catalog = catalog or Catalog()
    cell = catalog.cell(workload)
    if devices is None:
        devices = tpu_devices(cell["chips"])
    kind = catalog.kind(cell["kind"])
    clock = CompileClock()
    job = kind.setup({"cell": cell, "seed": int(seed), "devices": devices})
    setup_s = time.perf_counter() - t_start
    c0, n0 = clock.read()
    res = kind.run(job, float(seconds), bool(trace))
    c1, n1 = clock.read()
    log(window_compiles=n1 - n0, window_compile_s=c1 - c0, setup_compile_s=c0)
    device = device_record(devices)

    checks = reference.Checks()
    kind.check(job, checks)
    del job

    out = {
        "correct": checks.correct,
        "attempted": int(res["attempted"]),
        "failed": int(res["failed"]),
        "metrics": {},
        "device": device,
    }
    if trace:
        rctx = dict(res["trace_ctx"], cell=cell, device_kind=device["kind"])
        for m in catalog.per_layer(workload):
            value = catalog.reader(m["name"]).read(rctx)
            if value is not None:
                out["metrics"][m["name"]] = {"value": float(value), "unit": m["unit"]}
        out["device"]["busy_s"] = float(rctx["busy_s"])
        out["device"]["window_s"] = float(rctx["window_s"])
        out["breakdown"] = rctx["breakdown"]
    else:
        values = dict(res["metrics"], setup_s=setup_s)
        for m in catalog.end_to_end(workload):
            if m["name"] not in values:
                raise BenchError(f"{workload} produced no {m['name']}")
            out["metrics"][m["name"]] = {"value": float(values[m["name"]]), "unit": m["unit"]}
    out["checks"] = checks.as_dict()
    return out
