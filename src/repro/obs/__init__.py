"""Unified telemetry for the train + serve stacks.

Three layers, one import:

* **metrics registry** (:mod:`repro.obs.registry`) — counters / gauges /
  histograms under stable dotted namespaces with a labels dimension
  (replica id, arch group). Components hold a :class:`StatsView` over a
  registry instead of a free-floating stats dict; the names live ONCE in
  :mod:`repro.obs.names`.
* **span tracer** (:mod:`repro.obs.tracer`) — ``with obs.span("name"):``
  host-side nested spans into a ring buffer, exported as Perfetto-loadable
  Chrome trace-event JSON on the profiler's wall clock; every span also
  enters a ``jax.profiler.TraceAnnotation``, so a profiler trace however
  started shows it.
* **per-request timelines** — ``Completion.first_token`` + the TTFT/queue-
  wait percentiles in :mod:`repro.serve.metrics`, dumped alongside the
  registry snapshot by the launchers' ``--metrics-out`` / ``--trace-out``.

Module-level state: ONE process-global registry and ONE process-global
tracer, both disabled until :func:`configure` (driven by the launcher
flags) switches them on — a disabled registry/tracer is an attribute check
per call site, so default runs pay nothing. Serving components additionally
create private always-on registries for their own stats (the replacement
for the dicts tests and log lines already read); the launcher hands them
the shared run registry instead so fleet series aggregate under replica
labels.
"""
from repro.obs.names import (
    KV_GAUGES,
    OFL_HISTOGRAMS,
    OFL_METRICS,
    REQUEST_HISTOGRAMS,
    REQUIRED_SERVE_KEYS,
    ROUTER_METRICS,
    SERVE_ENGINE_METRICS,
    serve_namespace,
)
from repro.obs.registry import MetricsRegistry, StatsView
from repro.obs.tracer import SpanTracer

_registry = MetricsRegistry(enabled=False)
_tracer = SpanTracer()


def registry() -> MetricsRegistry:
    """The process-global registry (disabled until :func:`configure`)."""
    return _registry


def tracer() -> SpanTracer:
    """The process-global span tracer (disabled until :func:`configure`)."""
    return _tracer


def span(name: str, **args):
    """Open a span on the global tracer (no-op context when disabled)."""
    return _tracer.span(name, **args)


def instant(name: str, **args) -> None:
    """Zero-duration marker on the global tracer."""
    _tracer.instant(name, **args)


def observe(name: str, value: float, **labels) -> None:
    """Histogram observation on the global registry (no-op when disabled)."""
    _registry.observe(name, value, **labels)


def inc(name: str, value: float = 1, **labels) -> None:
    """Counter bump on the global registry (no-op when disabled)."""
    _registry.inc(name, value, **labels)


def configure(metrics: bool = False, trace: bool = False,
              profile_dir: str = None, trace_capacity: int = 65536) -> None:
    """Switch the process-global telemetry on/off (launcher flag plumbing).

    ``metrics`` enables the global registry, ``trace`` the span tracer (its
    ring is cleared and its clock origin taken anew), and ``profile_dir``
    starts a JAX profiler trace."""
    global _tracer
    _registry.enabled = metrics
    if trace and _tracer._events.maxlen != trace_capacity:
        _tracer = SpanTracer(capacity=trace_capacity)
    _tracer.enabled = trace
    if trace:
        _tracer.clear()
    if profile_dir:
        import jax

        jax.profiler.start_trace(profile_dir)


__all__ = [
    "MetricsRegistry",
    "StatsView",
    "SpanTracer",
    "KV_GAUGES",
    "OFL_HISTOGRAMS",
    "OFL_METRICS",
    "REQUEST_HISTOGRAMS",
    "REQUIRED_SERVE_KEYS",
    "ROUTER_METRICS",
    "SERVE_ENGINE_METRICS",
    "serve_namespace",
    "registry",
    "tracer",
    "span",
    "instant",
    "observe",
    "inc",
    "configure",
]
