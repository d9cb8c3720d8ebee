"""repro.obs — pipeline-wide observability: spans, metrics, stall attribution.

Zero-dependency (stdlib + numpy) instrumentation substrate shared by all
three engines (``PiperPipeline``, ``ShardedPiperPipeline``, the
``repro.stream`` service):

  * :mod:`repro.obs.trace`    — thread-safe nested span tracer exported
    as Chrome/Perfetto trace-event JSON, bridged into
    ``jax.profiler.TraceAnnotation`` so host spans line up with device
    profiles;
  * :mod:`repro.obs.counters` — counter/gauge/histogram registry with a
    ``snapshot()``/JSONL export contract (histograms carry exact
    count/sum plus a bounded percentile reservoir);
  * :mod:`repro.obs.stall`    — exhaustive wall-time attribution
    (queue-wait / host-assembly / device-dispatch / vocab-merge), the
    signal the multi-host autoscaler and e2e-overlap work read.

Default-on and provably non-semantic: instrumentation never touches the
computation (spans time host blocks; ``jax.named_scope`` only names
HLO), every golden/bit-identity test runs with it enabled, and
:func:`disable` reduces a span to a shared no-op context manager. Where
decode time goes is read from the device profile, not from a split of
the program.

The stream service writes one ``stream/request`` and one
``stream/batch`` record per routed request and batch into
:func:`tracer` (:meth:`Tracer.complete`); while a ``jax.profiler``
session runs the tracer leaves ``obs/clock/<perf_counter_ns>``
anchors in it, which map the ring onto the profile's clock.
"""

from __future__ import annotations

from repro.obs import counters as counters_lib
from repro.obs import stall  # noqa: F401  (re-export module)
from repro.obs import trace as trace_lib
from repro.obs.counters import Counter, Gauge, Histogram, Registry
from repro.obs.stall import StallClock
from repro.obs.trace import Tracer, validate_trace

_GLOBAL_TRACER = trace_lib.Tracer()


def tracer() -> Tracer:
    """The process-wide tracer every engine records into (one timeline)."""
    return _GLOBAL_TRACER


def span(name: str, cat: str = "host", **labels):
    """Record a nested span on the global tracer (context manager)."""
    return _GLOBAL_TRACER.span(name, cat=cat, **labels)


def instant(name: str, cat: str = "host", **labels) -> None:
    """Record an instant marker on the global tracer."""
    _GLOBAL_TRACER.instant(name, cat=cat, **labels)


def enable() -> None:
    _GLOBAL_TRACER.enabled = True


def disable() -> None:
    _GLOBAL_TRACER.enabled = False


def enabled() -> bool:
    return _GLOBAL_TRACER.enabled


def metrics() -> Registry:
    """The process-wide default metrics registry (engine-level counters;
    services own private registries — see :class:`Registry`)."""
    return counters_lib.default_registry()


__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "Registry",
    "StallClock",
    "Tracer",
    "disable",
    "enable",
    "enabled",
    "instant",
    "metrics",
    "span",
    "stall",
    "tracer",
    "validate_trace",
]
