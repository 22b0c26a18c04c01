"""Harness span tracer: wall-clock instrumentation of the pipeline.

Hook sites (``sweep_plan``, ``sim``, ``stream``, ``scenario``,
``benchmarks/run.py``) call the module-level :func:`span` /
:func:`interval` / :func:`instant` helpers, which are no-ops while
``TRACER`` is None — the disabled cost is one global read per call.
Enabled, every span records ``(track, name, t_start, duration, args,
thread)`` against a monotonic clock anchored at the tracer's creation.
The anchor is read from both clocks at one instant: ``t0_perf``
(``time.perf_counter``) and ``t0_wall_ns`` (``time.time_ns``), so
:meth:`SpanTracer.epoch_ns` puts a span on the epoch clock a profiler
trace stamps its own start with, and out-of-process sidecar events (the
``xc_worker`` compile server) land on the same timeline.

Spans recorded inside a :func:`batch` block carry its id as a ``batch``
argument, so the spans of one planned batch can be pulled out together.

Spans from different threads go to different trace rows (the exporter
keys tracks by ``(track, thread)``), so B/E pairs always nest properly —
a ``with span(...)`` block *is* the nesting.
"""
from __future__ import annotations

import contextlib
import contextvars
import threading
import time

__all__ = ["SpanTracer", "TRACER", "span", "interval", "instant", "batch"]

# id of the batch the current context works on (None outside any batch)
_BATCH: contextvars.ContextVar = contextvars.ContextVar("span_batch",
                                                        default=None)


def _with_batch(args: dict | None) -> dict | None:
    b = _BATCH.get()
    if b is None:
        return args
    return {**(args or {}), "batch": b}


class SpanTracer:
    def __init__(self, max_events: int = 200_000):
        self.t0_perf = time.perf_counter()
        self.t0_wall_ns = time.time_ns()
        self.t0_wall = self.t0_wall_ns * 1e-9
        self.max_events = max_events
        self._events: list = []
        self._dropped = 0
        self._lock = threading.Lock()

    def now_us(self) -> float:
        return (time.perf_counter() - self.t0_perf) * 1e6

    def perf_us(self, t_perf: float) -> float:
        """A ``time.perf_counter()`` reading on this tracer's clock (us)."""
        return (t_perf - self.t0_perf) * 1e6

    def epoch_ns(self, ts_us: float) -> int:
        """A span time (us on this tracer's clock) as epoch nanoseconds."""
        return self.t0_wall_ns + round(ts_us * 1e3)

    def _add(self, ev: tuple) -> None:
        with self._lock:
            if len(self._events) >= self.max_events:
                self._dropped += 1
                return
            self._events.append(ev)

    def complete(self, track: str, name: str, ts_us: float, dur_us: float,
                 args: dict | None = None) -> None:
        """One finished span (exported as a B/E pair)."""
        self._add(("span", track, name, ts_us, max(dur_us, 0.0),
                   _with_batch(args), threading.get_ident()))

    def instant(self, track: str, name: str, args: dict | None = None,
                ts_us: float | None = None) -> None:
        ts = self.now_us() if ts_us is None else ts_us
        self._add(("instant", track, name, ts, 0.0, _with_batch(args),
                   threading.get_ident()))

    def drain(self) -> list:
        """All recorded events (sorted by start time); tracer keeps them —
        export is repeatable."""
        with self._lock:
            evs = sorted(self._events, key=lambda e: e[3])
            if self._dropped:
                evs.append(("instant", "tracer", "events_dropped",
                            self.now_us(), 0.0,
                            {"dropped": self._dropped},
                            threading.get_ident()))
            return evs


# The one process-wide tracer; None = disabled (see ``repro.obs``).
TRACER: SpanTracer | None = None


@contextlib.contextmanager
def span(track: str, name: str, **args):
    """``with span("compile", "ensure_compiled", key=...):`` — no-op when
    tracing is off."""
    tr = TRACER
    if tr is None:
        yield
        return
    t0 = tr.now_us()
    try:
        yield
    finally:
        tr.complete(track, name, t0, tr.now_us() - t0, args or None)


def interval(track: str, name: str, t0: float, t1: float, **args) -> None:
    """A span the caller timed itself with ``time.perf_counter()`` from
    ``t0`` to ``t1`` (a stage that also feeds an always-on timer)."""
    tr = TRACER
    if tr is not None:
        tr.complete(track, name, tr.perf_us(t0), (t1 - t0) * 1e6,
                    args or None)


def instant(track: str, name: str, **args) -> None:
    tr = TRACER
    if tr is not None:
        tr.instant(track, name, args or None)


@contextlib.contextmanager
def batch(batch_id: int):
    """Tag every span recorded in this context with ``batch=batch_id``."""
    token = _BATCH.set(batch_id)
    try:
        yield
    finally:
        _BATCH.reset(token)
