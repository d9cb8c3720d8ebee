"""Device trace: capture with ``jax.profiler`` and reduce to metrics.

A capture is normalized to plain lists so that the reduction can be
tested on a recorded trace without a chip:

    {"devices": {"0": {"modules": [[name, start_ns, dur_ns], ...],
                       "ops":     [[name, start_ns, dur_ns], ...]}},
     "host":    [[name, start_ns, dur_ns], ...]}

``modules`` are the executions of compiled programs (the device plane's
"XLA Modules" line), ``ops`` the operations inside them ("XLA Ops").
``host`` keeps the host spans the program and the benchmark open
(``jax.profiler.TraceAnnotation`` names such as ``loop1/chunk`` or
``bench/job``); all times are on the profiler's one clock.

Busy time is the union of op intervals; a program's device time is the
sum of its module executions; an idle gap is labelled by the innermost
host span open at its midpoint.
"""

from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_HOST_SPAN = re.compile(r"^[a-z0-9_]+(/[a-z0-9_]+)+$")
_SUFFIX = re.compile(r"\(\d+\)$")
NO_SPAN = "(no host span)"


class Capture:
    """Record a device trace into a temporary directory (under ``TMPDIR``).

    ``start()`` and ``stop()`` bracket the traced stretch; ``load()``
    then normalizes the trace and deletes its files. As a context
    manager it does all three."""

    def __init__(self):
        self.trace: dict | None = None
        self._dir = None

    def start(self) -> None:
        from jax import profiler

        self._dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        profiler.start_trace(self._dir, profiler_options=opts)

    def stop(self) -> None:
        from jax import profiler

        profiler.stop_trace()

    def load(self) -> dict:
        try:
            paths = glob.glob(os.path.join(self._dir, "**", "*.xplane.pb"), recursive=True)
            if len(paths) != 1:
                raise RuntimeError(f"expected one xplane file, found {paths}")
            self.trace = load_xplane(paths[0])
        finally:
            self.discard()
        return self.trace

    def discard(self) -> None:
        if self._dir is not None:
            shutil.rmtree(self._dir, ignore_errors=True)
            self._dir = None

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, exc_type, *_):
        try:
            self.stop()
        except BaseException:
            self.discard()
            raise
        if exc_type is None:
            self.load()
        else:
            self.discard()
        return False


def program_name(module_event_name: str) -> str:
    """``jit_vocab_step(123)`` -> ``jit_vocab_step``."""
    return _SUFFIX.sub("", module_event_name)


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, dict] = {}
    host: list = []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(m.group(1), {"modules": [], "ops": []})
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(line.name)
                if key:
                    dev[key] += [[e.name, e.start_ns, e.duration_ns] for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [
                    [e.name, e.start_ns, e.duration_ns]
                    for e in line.events
                    if e.duration_ns > 0 and _HOST_SPAN.match(e.name)
                ]
    return {"devices": devices, "host": host}


# ---------------------------------------------------------------------- #
# reduction
# ---------------------------------------------------------------------- #
def window_of(trace: dict, span_name: str) -> tuple[float, float]:
    """``(start_ns, end_ns)`` of the host span ``span_name`` (the first
    one if it occurs more than once)."""
    for name, start, dur in trace["host"]:
        if name == span_name:
            return float(start), float(start + dur)
    raise ValueError(f"no host span {span_name!r} in the trace")


def _clip(events, window):
    lo, hi = window
    out = []
    for name, start, dur in events:
        s, e = max(float(start), lo), min(float(start + dur), hi)
        if e > s:
            out.append((name, s, e))
    return out


def _union(intervals) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted((s, e) for _, s, e in intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_seconds(trace: dict, window) -> float:
    """Seconds in ``window`` during which an op ran, averaged over devices."""
    devs = trace["devices"].values()
    if not devs:
        return 0.0
    total = 0.0
    for d in devs:
        total += sum(e - s for s, e in _union(_clip(d["ops"], window)))
    return total / len(devs) * 1e-9


def idle_share(trace: dict, window) -> float:
    length = (window[1] - window[0]) * 1e-9
    return 1.0 - busy_seconds(trace, window) / length


def program_seconds(trace: dict, window, match) -> tuple[float, int]:
    """Device seconds and executions of the programs ``match(name)``
    selects, among modules that start inside ``window``; seconds are
    summed over devices, executions counted on each device and averaged."""
    lo, hi = window
    secs, runs = 0.0, 0
    for d in trace["devices"].values():
        for name, start, dur in d["modules"]:
            if lo <= start < hi and match(program_name(name)):
                secs += dur * 1e-9
                runs += 1
    n = max(len(trace["devices"]), 1)
    return secs, runs / n


def program_table(trace: dict, window) -> dict[str, float]:
    """Device seconds per program name in ``window`` (summed over devices)."""
    lo, hi = window
    out: dict[str, float] = {}
    for d in trace["devices"].values():
        for name, start, dur in d["modules"]:
            if lo <= start < hi:
                p = program_name(name)
                out[p] = out.get(p, 0.0) + dur * 1e-9
    return out


_OP_KIND = re.compile(r" ([a-z][a-z0-9_-]*)\(")


def op_label(hlo_text: str) -> str:
    """``%fusion.1 = s32[...] fusion(...), ...`` -> ``%fusion.1 fusion``."""
    lhs, _, rhs = hlo_text.partition(" = ")
    m = _OP_KIND.search(" " + rhs)
    return f"{lhs} {m.group(1)}" if m else lhs[:80]


def _with_program(device: dict, window) -> list:
    """The device's ops in ``window`` as ``(program op, start, end)``, each
    named by the module execution it falls in."""
    mods = sorted((float(s), float(s + d), program_name(n)) for n, s, d in device["modules"])
    out, k = [], 0
    for name, s, e in sorted(_clip(device["ops"], window), key=lambda x: x[1]):
        while k < len(mods) and mods[k][1] <= s:
            k += 1
        prog = mods[k][2] if k < len(mods) and mods[k][0] <= s else "?"
        out.append((f"{prog} {op_label(name)}", s, e))
    return out


def top_ops(trace: dict, window, n: int = 10) -> list:
    """The ``n`` device operations that took most time in ``window``:
    ``[program op, seconds]`` summed over calls, averaged over devices."""
    tot: dict[str, float] = {}
    for d in trace["devices"].values():
        for name, s, e in _with_program(d, window):
            tot[name] = tot.get(name, 0.0) + (e - s) * 1e-9
    k = max(len(trace["devices"]), 1)
    return [[name, v / k] for name, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def _innermost_spans(host: list, times: list[float]) -> list[str]:
    """For each time, the name of the shortest host span open at it (a
    sweep over span starts and ends, with a heap of the open spans)."""
    import heapq

    spans = sorted((float(s), float(s + d), float(d), name) for name, s, d in host)
    order = sorted(range(len(times)), key=lambda i: times[i])
    out = [NO_SPAN] * len(times)
    heap: list = []
    k = 0
    for i in order:
        t = times[i]
        while k < len(spans) and spans[k][0] <= t:
            s, e, d, name = spans[k]
            heapq.heappush(heap, (d, e, name))
            k += 1
        # drop spans that closed before t; any left on top is innermost
        while heap and heap[0][1] <= t:
            heapq.heappop(heap)
        if heap:
            out[i] = heap[0][2]
    return out


def idle_gaps(trace: dict, window, n: int = 10) -> list:
    """Idle time in ``window`` grouped by the innermost host span open at
    each gap's midpoint: ``[label, seconds]`` for the ``n`` largest
    groups, averaged over devices."""
    host = [h for h in trace["host"] if not h[0].startswith("bench/window")]
    tot: dict[str, float] = {}
    lo, hi = window
    for d in trace["devices"].values():
        edges = [lo]
        for s, e in _union(_clip(d["ops"], window)):
            edges += [s, e]
        edges.append(hi)
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        labels = _innermost_spans(host, [(a + b) / 2 for a, b in gaps])
        for (a, b), label in zip(gaps, labels):
            tot[label] = tot.get(label, 0.0) + (b - a) * 1e-9
    k = max(len(trace["devices"]), 1)
    return [[name, v / k] for name, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


# ---------------------------------------------------------------------- #
# the program's jitted entry points, by layer
# ---------------------------------------------------------------------- #
LOOP1_PROGRAMS = ("vocab_step", "_shard_states")
LOOP2_PROGRAMS = ("transform_chunk", "_sharded_transform")


def is_loop1(program: str) -> bool:
    return any(p in program for p in LOOP1_PROGRAMS)


def is_loop2(program: str) -> bool:
    return any(p in program for p in LOOP2_PROGRAMS)


def summary(trace: dict, window) -> dict:
    """What every traced run reports beside its metrics: busy and window
    seconds, and the breakdown of device time and idle gaps."""
    return {
        "busy_s": busy_seconds(trace, window),
        "window_s": (window[1] - window[0]) * 1e-9,
        "breakdown": {"device_ops": top_ops(trace, window), "idle_gaps": idle_gaps(trace, window)},
    }
