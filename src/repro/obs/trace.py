"""Thread-safe span tracer with Chrome/Perfetto trace-event export.

The host-side timeline of the preprocessing pipeline: nested spans with
string labels, recorded into a bounded ring and exported as the Chrome
trace-event JSON that ``ui.perfetto.dev`` / ``chrome://tracing`` load
directly (`{"traceEvents": [...]}` with ``ph:"X"`` complete events —
nesting is implied by containment of ``[ts, ts+dur]`` within a thread
track, so the service loop's ``stream/step`` → ``host/assemble`` →
``loop2/dispatch`` hierarchy renders as a flame graph per thread).

Alignment with device profiles: every span also enters a
``jax.profiler.TraceAnnotation`` (when the profiler is importable), so
if the run is captured with ``jax.profiler.trace()`` the same span names
appear on the XLA host track of the device profile — one vocabulary of
names across both tools. Device-*internal* stage labels (decode /
modulus / scatter inside a jitted program) come from ``jax.named_scope``
annotations at the instrumentation sites (``core/pipeline.py``), which
name the lowered HLO rather than host wall time.

Semantics (documented, not implied): a span measures **host wall time of
the enclosed block**. For an async JAX dispatch that is the time to
*launch* the computation, not to finish it — device completion shows up
in the service's ``stream/ready`` spans and in the stall attribution
(:mod:`repro.obs.stall`).

One clock: every time the tracer takes is ``time.perf_counter_ns``.
Event ``ts``/``dur`` are microseconds from the tracer's epoch
(:attr:`Tracer.epoch_ns`), so ``epoch_ns + ts * 1e3`` is the event's
start on that clock. A record whose life crosses threads (a request
from submit to routed) is written with explicit stamps through
:meth:`Tracer.complete`.

Anchors to the device trace: while a ``jax.profiler`` session runs, the
tracer enters a short ``TraceAnnotation`` named ``obs/clock/<ns>``,
where ``<ns>`` is the ``perf_counter_ns`` it read just before — at the
first span or record it sees in a session and every
``ANCHOR_EVERY_NS`` after. The annotation's start in the profile minus
``<ns>`` maps ring times onto the profile's clock. With no session
running, the check is one call and no anchor is left.

Tracing is **default-on** with a bounded ring (oldest events drop, a
counter records how many) and negligible overhead: one clock pair plus
one deque append per span. ``Tracer.enabled = False`` (or
:func:`repro.obs.disable`) turns a span into a shared no-op context
manager and :meth:`Tracer.complete` into a return.

Run as a module to validate a trace file against the schema::

    python -m repro.obs.trace out.json
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque

# Bounded ring: 64Ki events ≈ a few MB of host memory at the rate the
# engines emit (a handful of spans per chunk).
DEFAULT_MAX_EVENTS = 1 << 16

# Clock anchors: name prefix and the least spacing while a profiler
# session runs; each anchor annotation lasts ANCHOR_NS.
ANCHOR_PREFIX = "obs/clock/"
ANCHOR_EVERY_NS = 250_000_000
ANCHOR_NS = 1_000

_VALID_PH = {"X", "i", "I", "M", "C", "B", "E"}


def _annotation_cls():
    """jax.profiler.TraceAnnotation when importable, else None (bare
    installs / stripped builds keep working — spans just skip the
    profiler bridge)."""
    try:
        from jax.profiler import TraceAnnotation

        return TraceAnnotation
    except Exception:  # pragma: no cover — bare installs only
        return None


def _profiler_active_fn():
    """A no-argument callable telling whether a ``jax.profiler`` session
    is recording (``TraceMe.is_enabled``); None when unavailable."""
    try:
        from jax._src.lib import _profiler

        return _profiler.TraceMe.is_enabled
    except Exception:  # pragma: no cover — bare installs only
        return None


class _NullSpan:
    """Shared no-op context manager for disabled tracers."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    """One live span: records a ``ph:"X"`` event on exit."""

    __slots__ = ("_tracer", "name", "cat", "args", "_t0", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, cat: str, args: dict):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self._t0 = 0.0
        self._annotation = None

    def __enter__(self):
        cls = self._tracer._annotation
        if cls is not None:
            self._annotation = cls(self.name)
            self._annotation.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        self._tracer._record(self.name, self.cat, self._t0, t1, self.args)
        self._tracer._anchor()
        return False


class Tracer:
    """Bounded, thread-safe trace-event recorder.

    Args:
      max_events: ring capacity; the oldest events drop beyond it and
        ``dropped`` counts them (the export embeds the count as process
        metadata so a truncated trace is self-describing).
      annotate: bridge spans into ``jax.profiler.TraceAnnotation`` so
        host spans line up with device profiles (auto-off when the
        profiler is not importable).
    """

    def __init__(self, max_events: int = DEFAULT_MAX_EVENTS, annotate: bool = True):
        self.enabled = True
        self._lock = threading.Lock()
        self._events: deque = deque(maxlen=max_events)
        self._appended = 0
        self._t_epoch = time.perf_counter_ns()
        self._annotation = _annotation_cls() if annotate else None
        self._profiler_active = _profiler_active_fn() if self._annotation else None
        self._last_anchor: int | None = None  # None: no anchor this session
        self._thread_names: dict[int, str] = {}

    @property
    def epoch_ns(self) -> int:
        """``perf_counter_ns`` at which event ``ts`` is 0."""
        return self._t_epoch

    # -- recording ----------------------------------------------------- #
    def span(self, name: str, cat: str = "host", **labels):
        """Context manager timing the enclosed block as one complete
        event. ``labels`` become the event's ``args`` (stringified)."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, cat, labels)

    def complete(
        self, name: str, start_ns: int, end_ns: int, cat: str = "host", **labels
    ) -> None:
        """Record one complete event from explicit ``perf_counter_ns``
        stamps, for a life that no single block on one thread spans
        (the service's ``stream/request`` and ``stream/batch``
        records). Not bridged to the profiler; ``labels`` become args."""
        if not self.enabled:
            return
        self._record(name, cat, start_ns, end_ns, labels)
        self._anchor()

    def instant(self, name: str, cat: str = "host", **labels) -> None:
        """Zero-duration marker (``ph:"i"``) — vocab refresh arrivals,
        swap applications, error events."""
        if not self.enabled:
            return
        ts = (time.perf_counter_ns() - self._t_epoch) / 1e3
        self._append(
            {
                "name": name,
                "cat": cat,
                "ph": "i",
                "s": "t",  # thread-scoped instant
                "ts": ts,
                "pid": os.getpid(),
                "tid": threading.get_ident(),
                "args": {k: _argstr(v) for k, v in labels.items()},
            }
        )

    def _anchor(self) -> None:
        """Leave an ``obs/clock/<ns>`` annotation in a running profiler
        session: at the first call that sees the session, then at most
        every ``ANCHOR_EVERY_NS``."""
        if self._profiler_active is None:
            return
        if not self._profiler_active():
            self._last_anchor = None
            return
        t = time.perf_counter_ns()
        last = self._last_anchor
        if last is not None and t - last < ANCHOR_EVERY_NS:
            return
        self._last_anchor = t
        with self._annotation(f"{ANCHOR_PREFIX}{t}"):
            # a non-zero length: the profile's readers drop empty events
            while time.perf_counter_ns() - t < ANCHOR_NS:
                pass

    def _record(self, name, cat, t0, t1, labels) -> None:
        self._append(
            {
                "name": name,
                "cat": cat,
                "ph": "X",
                "ts": (t0 - self._t_epoch) / 1e3,
                "dur": (t1 - t0) / 1e3,
                "pid": os.getpid(),
                "tid": threading.get_ident(),
                "args": {k: _argstr(v) for k, v in labels.items()},
            }
        )

    def _append(self, event: dict) -> None:
        tid = event["tid"]
        with self._lock:
            if tid not in self._thread_names:
                t = threading.current_thread()
                self._thread_names[tid] = t.name
            self._events.append(event)
            self._appended += 1

    # -- inspection / export ------------------------------------------- #
    @property
    def dropped(self) -> int:
        with self._lock:
            return max(0, self._appended - len(self._events))

    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def reset(self) -> None:
        with self._lock:
            self._events.clear()
            self._appended = 0
            self._t_epoch = time.perf_counter_ns()

    def to_chrome(self) -> dict:
        """The Perfetto-loadable document: thread-name metadata events
        first, then the recorded events in arrival order."""
        with self._lock:
            events = list(self._events)
            names = dict(self._thread_names)
            dropped = max(0, self._appended - len(events))
        pid = os.getpid()
        meta: list[dict] = [
            {
                "name": "process_name",
                "ph": "M",
                "pid": pid,
                "tid": 0,
                "args": {"name": "repro-preprocess"},
            }
        ]
        for tid, tname in sorted(names.items()):
            meta.append(
                {
                    "name": "thread_name",
                    "ph": "M",
                    "pid": pid,
                    "tid": tid,
                    "args": {"name": tname},
                }
            )
        return {
            "traceEvents": meta + events,
            "displayTimeUnit": "ms",
            "otherData": {"dropped_events": dropped},
        }

    def export(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)


def _argstr(v):
    """Event args must be JSON scalars; keep numbers, stringify the rest."""
    return v if isinstance(v, (int, float, bool, str)) else str(v)


# --------------------------------------------------------------------- #
# schema validation (the CI obs job runs this over the smoke trace)
# --------------------------------------------------------------------- #
def validate_trace(doc: dict) -> list[str]:
    """Structural check against the trace-event format. Returns a list
    of problems (empty = Perfetto-loadable as far as the schema goes)."""
    errors: list[str] = []
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        return ["document must be an object with a 'traceEvents' array"]
    events = doc["traceEvents"]
    if not isinstance(events, list):
        return ["'traceEvents' must be an array"]
    if not events:
        errors.append("'traceEvents' is empty")
    for i, ev in enumerate(events):
        where = f"traceEvents[{i}]"
        if not isinstance(ev, dict):
            errors.append(f"{where}: not an object")
            continue
        ph = ev.get("ph")
        if ph not in _VALID_PH:
            errors.append(f"{where}: bad ph {ph!r}")
            continue
        if not isinstance(ev.get("name"), str) or not ev["name"]:
            errors.append(f"{where}: missing name")
        if not isinstance(ev.get("pid"), int) or not isinstance(
            ev.get("tid"), int
        ):
            errors.append(f"{where}: pid/tid must be ints")
        if ph in ("X", "i", "I", "B", "E", "C"):
            if not isinstance(ev.get("ts"), (int, float)):
                errors.append(f"{where}: {ph} event needs numeric ts")
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                errors.append(f"{where}: X event needs non-negative dur")
        args = ev.get("args", {})
        if not isinstance(args, dict):
            errors.append(f"{where}: args must be an object")
    return errors


def _main(argv: list[str]) -> int:
    if len(argv) != 1:
        print("usage: python -m repro.obs.trace <trace.json>")
        return 2
    with open(argv[0]) as f:
        doc = json.load(f)
    errors = validate_trace(doc)
    n = len(doc.get("traceEvents", [])) if isinstance(doc, dict) else 0
    if errors:
        for e in errors:
            print(f"INVALID: {e}")
        return 1
    print(f"OK: {argv[0]} — {n} trace events, schema valid")
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(_main(sys.argv[1:]))
