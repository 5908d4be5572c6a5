"""Host-side span tracer: nested ``with obs.span("decode_chunk"): ...``
regions recorded into a bounded ring buffer and exported as Chrome
trace-event JSON (loadable in Perfetto / ``chrome://tracing``).

Contract with the serving hot path: a span brackets one HOST action (a
dispatch, a routing decision, an adoption scatter) — it never forces a
device sync, so the engine's O(1)-host-syncs-per-chunk invariant is
untouched whether tracing is on or off. When the tracer is disabled
(the default), :meth:`SpanTracer.span` returns a shared no-op context
manager: the cost of an instrumented call site is one attribute check.

Events use the Chrome trace-event "complete" phase (``ph: "X"``): each
record carries its own start timestamp and duration in microseconds plus
the recording thread id, so nesting is containment — Perfetto stacks spans
per thread without any explicit parent links. We additionally record the
enclosing span's name in ``args.parent`` (from a per-thread stack) so tests
and offline tooling can assert nesting without reconstructing intervals.

Every span of an enabled tracer also enters a
:class:`jax.profiler.TraceAnnotation` of the same name, so whichever way a
JAX profiler trace was started (``launch --profile-dir``,
``jax.profiler.trace``, an on-demand capture) its host plane holds the
spans beside the XLA device trace. The exported JSON is on the same clock:
``ts`` is wall-clock microseconds (``time.time_ns()`` taken at
:meth:`SpanTracer.clear`, plus ``perf_counter_ns`` since), as the
profiler's own timestamps are; durations are ``perf_counter_ns``.
"""
from __future__ import annotations

import json
import threading
import time
from collections import deque
from typing import Deque, List, Optional


class _NullSpan:
    """Shared no-op context manager — the disabled-tracer fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("_tracer", "name", "args", "_t0", "_ann")

    def __init__(self, tracer: "SpanTracer", name: str, args: dict):
        self._tracer = tracer
        self.name = name
        self.args = args
        self._t0 = 0
        self._ann = None

    def __enter__(self):
        import jax

        tr = self._tracer
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        stack = tr._stack()
        if stack:
            self.args.setdefault("parent", stack[-1])
        stack.append(self.name)
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        tr = self._tracer
        stack = tr._stack()
        if stack and stack[-1] == self.name:
            stack.pop()
        tr._record(self.name, self._t0, t1, self.args)
        self._ann.__exit__(*exc)
        return False


class SpanTracer:
    """Ring-buffered host span recorder with Chrome trace-event export."""

    def __init__(self, capacity: int = 65536):
        self.enabled = False
        self._events: Deque[dict] = deque(maxlen=capacity)
        self._local = threading.local()
        self._lock = threading.Lock()
        self._set_origin()

    def _set_origin(self) -> None:
        """Pair the wall clock with ``perf_counter_ns``: ``ts`` is the wall
        clock at the origin plus the ``perf_counter_ns`` elapsed since."""
        self._origin_unix_ns = time.time_ns()
        self._origin_ns = time.perf_counter_ns()

    # -- recording -----------------------------------------------------------

    def span(self, name: str, **args):
        """Open a span; disabled tracers hand back a shared no-op."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, args)

    def _stack(self) -> List[str]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _record(self, name: str, t0_ns: int, t1_ns: int, args: dict) -> None:
        ev = {
            "name": name,
            "ph": "X",
            "ts": (self._origin_unix_ns + t0_ns - self._origin_ns) / 1e3,  # wall-clock µs
            "dur": (t1_ns - t0_ns) / 1e3,
            "pid": 0,
            "tid": threading.get_ident(),
        }
        if args:
            ev["args"] = {k: _jsonable(v) for k, v in args.items()}
        with self._lock:
            self._events.append(ev)

    def instant(self, name: str, **args) -> None:
        """A zero-duration marker event (arrivals, evictions)."""
        if not self.enabled:
            return
        t = time.perf_counter_ns()
        self._record(name, t, t, args)

    # -- lifecycle -----------------------------------------------------------

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
        self._set_origin()

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    # -- export --------------------------------------------------------------

    def events(self) -> List[dict]:
        """Events sorted by start time (ties: longest span first, so a parent
        precedes the children it contains). The ring records at span EXIT —
        children land before their parents — so raw buffer order is not
        start-ordered; the export re-sorts, which also makes per-thread ``ts``
        monotonic for the validator."""
        with self._lock:
            evs = list(self._events)
        return sorted(evs, key=lambda e: (e["ts"], -e.get("dur", 0.0)))

    def to_chrome_trace(self) -> dict:
        """The Chrome trace-event JSON object Perfetto loads directly."""
        return {
            "traceEvents": self.events(),
            "displayTimeUnit": "ms",
            "otherData": {"recorder": "repro.obs.tracer", "clock": "unix",
                          "origin_unix_ns": self._origin_unix_ns},
        }

    def dump(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)


def _jsonable(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return str(v)
