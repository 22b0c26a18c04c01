"""Unified observability: SSD flight recorder + harness telemetry.

Two layers exporting into ONE Chrome-trace-event JSON (Perfetto /
``chrome://tracing`` loadable):

* **Layer 1 — SSD flight recorder** (``events.py``): per-transaction
  timelines and per-resource occupancy intervals, reconstructed *host-side*
  from the scan's existing ``StepOut`` arrays after execution.  The jitted
  step carries nothing new — executables, cache keys and every figure CSV
  are byte-identical with the recorder on or off.
* **Layer 2 — harness telemetry** (``spans.py`` + ``registry.py``): span
  instrumentation of the plan → lower → compile → dispatch pipeline and the
  streaming window loop, plus a structured metrics registry backing the
  process-wide ``bench.PERF`` scoreboard (``PERF`` stays a dict view, so
  the BENCH_*.json schema is unchanged).

Both layers are **off by default** and cost one ``is None`` check at each
hook site when disabled.  ``enable_tracing()`` arms them;
``export_trace()`` writes the combined trace (and optionally the
resource-utilization heatmap CSV).  This package imports only numpy and
the stdlib — never jax — so hooking it into the hot modules is free.
"""
from __future__ import annotations

from repro.obs import events as _events
from repro.obs import heatmap as _heatmap
from repro.obs import spans as _spans
from repro.obs.export import TraceBuilder, validate_trace

__all__ = [
    "enable_tracing", "disable_tracing", "tracing_enabled",
    "export_trace", "validate_trace", "TraceBuilder",
]


def enable_tracing(max_txn_events: int | None = None,
                   recorder: bool = True) -> _spans.SpanTracer:
    """Arm both layers: install the global SSD recorder and span tracer,
    and return the span tracer.

    ``max_txn_events`` caps the number of per-transaction SSD events
    retained (runs past the cap are recorded as dropped, never silently
    truncated mid-run).  ``recorder=False`` arms the span tracer alone:
    timing a sweep against a profiler trace must not also pay for
    per-transaction recording.
    """
    if recorder:
        kwargs = {}
        if max_txn_events is not None:
            kwargs["max_txns"] = max_txn_events
        _events.RECORDER = _events.DeviceRecorder(**kwargs)
    _spans.TRACER = _spans.SpanTracer()
    return _spans.TRACER


def disable_tracing() -> None:
    """Disarm both layers (hook sites return to the no-op path)."""
    _events.RECORDER = None
    _spans.TRACER = None


def tracing_enabled() -> bool:
    return _events.RECORDER is not None or _spans.TRACER is not None


def export_trace(path: str, heatmap_csv: str | None = None,
                 bucket_us: float | None = None) -> dict:
    """Write the combined trace JSON (SSD + harness tracks) to ``path``.

    Returns a summary dict (event/track counts).  ``heatmap_csv`` also
    writes the resource x time-bucket utilization/conflict matrices
    (``heatmap.write_heatmap_csv``); ``bucket_us`` overrides the bucket
    width (default: ~120 buckets across the longest run).
    """
    builder = TraceBuilder()
    tracer = _spans.TRACER
    if tracer is not None:
        builder.add_harness_spans(tracer.drain())
    recorder = _events.RECORDER
    runs = recorder.finalized_runs() if recorder is not None else []
    for run in runs:
        builder.add_ssd_run(run)
    summary = builder.write(path)
    if heatmap_csv is not None:
        _heatmap.write_heatmap_csv(heatmap_csv, runs, bucket_us=bucket_us)
        summary["heatmap_csv"] = heatmap_csv
    return summary
