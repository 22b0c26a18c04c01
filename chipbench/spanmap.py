"""Program spans on the device trace's clock, and the idle time they name.

The program's span tracer (``repro.obs.spans``) stamps spans on the
host's monotonic clock, anchored to the epoch clock at one instant
(``SpanTracer.t0_wall_ns``).  A profiler trace stamps device ops in
nanoseconds from its own start, whose epoch time its ``Task Environment``
plane holds (``profile_start_time``).  So:

* ``load`` is ``profile.load`` plus ``profile_start_time``;
* ``device_clock`` puts drained spans on the trace's clock;
* ``reduce`` is ``profile.reduce`` with its idle time named after program
  spans: each idle interval of at least ``profile.SHORT_GAP_NS`` between
  device ops, and the time between the traced window's edges and the
  first and last device op, goes under the innermost span that covers it
  (``idle under <span>``), or under ``OUTSIDE`` where no span does (the
  harness's own work, such as generating the next traces).  Shorter gaps
  keep ``profile.SHORT_GAP``.  Without spans it returns ``profile.reduce``'s
  output unchanged;
* ``clock_offsets`` measures how far device ops fall outside the ``run``
  span of the group that launched them: the error of the shared clock.
"""
from __future__ import annotations

import bisect
import gzip

import numpy as np

from chipbench import profile

ENV_PLANE = "Task Environment"
OUTSIDE = "idle outside program spans"


def start_time(path: str) -> int:
    """Epoch nanoseconds at which the profiler trace under ``path``
    started: the origin of its device ops' ``start_ns``."""
    from jax.profiler import ProfileData

    f = profile._xspace_file(path)
    with open(f, "rb") as fh:
        raw = fh.read()
    if f.endswith(".gz"):
        raw = gzip.decompress(raw)
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if plane.name == ENV_PLANE:
            stats = dict(plane.stats)
            if "profile_start_time" in stats:
                return int(stats["profile_start_time"])
    raise ValueError(f"no profile_start_time in {f}")


def load(path: str) -> dict:
    """``{"devices": ..., "profile_start_time": epoch ns}``."""
    return dict(profile.load(path), profile_start_time=start_time(path))


def device_clock(events: list, t0_wall_ns: int, start_ns: int) -> list:
    """``SpanTracer.drain()`` spans as ``(name, start_ns, end_ns, args)``
    on the clock of a trace that started at epoch ``start_ns``, for a
    tracer anchored at epoch ``t0_wall_ns``."""
    base = t0_wall_ns - start_ns
    return [(name, base + ts * 1e3, base + (ts + dur) * 1e3, args)
            for kind, _track, name, ts, dur, args, _thread in events
            if kind == "span"]


def _name_interval(a: float, b: float, spans: list, starts: list,
                   add) -> None:
    """Split ``[a, b)`` by the spans that cover it; each piece goes under
    its innermost span (the latest started, then the first to end)."""
    hit = [s for s in spans[:bisect.bisect_left(starts, b)] if s[2] > a]
    cuts = sorted({a, b} | {t for s in hit for t in (s[1], s[2])
                            if a < t < b})
    for x, y in zip(cuts, cuts[1:]):
        cover = [s for s in hit if s[1] <= x and s[2] >= y]
        if cover:
            inner = max(cover, key=lambda s: (s[1], -s[2]))
            add(f"idle under {inner[0]}", y - x)
        else:
            add(OUTSIDE, y - x)


def name_idle(trace: dict, window_s: float, spans: list,
              window_start_ns: float = 0.0) -> dict:
    """Idle nanoseconds per name, averaged over the devices, of a window
    of ``window_s`` seconds from ``window_start_ns`` on the trace's clock.
    The same idle time ``profile.reduce`` counts, named by ``spans``."""
    spans = sorted(spans, key=lambda s: s[1])
    starts = [s[1] for s in spans]
    n_dev = len(trace["devices"])
    window_ns = window_s * 1e9
    idle: dict = {}

    def add(name, ns):
        idle[name] = idle.get(name, 0.0) + ns / n_dev

    for ops in trace["devices"].values():
        merged = profile.union([(s, e) for _, s, e in ops])
        for (_, end), (s, _) in zip(merged, merged[1:]):
            if s - end < profile.SHORT_GAP_NS:
                add(profile.SHORT_GAP, s - end)
            else:
                _name_interval(end, s, spans, starts, add)
        if not merged:
            _name_interval(window_start_ns, window_start_ns + window_ns,
                           spans, starts, add)
            continue
        first, last = merged[0][0], merged[-1][1]
        edges = max(0.0, window_ns - (last - first))
        lead = min(max(0.0, first - window_start_ns), edges)
        if lead > 0:
            _name_interval(first - lead, first, spans, starts, add)
        if edges - lead > 0:
            _name_interval(last, last + edges - lead, spans, starts, add)
    return idle


def reduce(trace: dict, window_s: float, kernels: dict,
           spans: list | None = None, window_start_ns: float = 0.0) -> dict:
    """``profile.reduce``; with ``spans`` (``device_clock``'s form) its
    ``idle_gaps`` name the idle time after them."""
    red = profile.reduce(trace, window_s, kernels)
    if spans is None:
        return red
    idle = name_idle(trace, window_s, spans, window_start_ns)
    top = sorted(idle.items(), key=lambda kv: -kv[1])[:profile.TOP]
    return dict(red, idle_gaps=[[n, v * 1e-9] for n, v in top],
                idle_s=sum(idle.values()) * 1e-9,
                unnamed_idle_s=idle.get(OUTSIDE, 0.0) * 1e-9)


def clock_offsets(trace: dict, run_spans: list) -> dict:
    """How far device ops fall outside the ``run`` spans that launched
    them.  Each op belongs to the latest run span that started before it
    (the first, for an op before every span); ``outside_ns`` is how far
    it reaches before that span's start or past its end.  ``lead_ns`` /
    ``tail_ns``: per span with ops, from its start to its first op and
    from its last op to its end (negative where an op lies outside)."""
    runs = sorted(run_spans, key=lambda s: s[1])
    if not runs:
        raise ValueError("no run spans")
    rs = np.asarray([s[1] for s in runs])
    re = np.asarray([s[2] for s in runs])
    ops = [o for v in trace["devices"].values() for o in v]
    os_ = np.asarray([o[1] for o in ops], float)
    oe = np.asarray([o[2] for o in ops], float)
    idx = np.clip(np.searchsorted(rs, os_, side="right") - 1, 0, None)
    outside = np.maximum(0.0, np.maximum(rs[idx] - os_, oe - re[idx]))
    lead, tail = [], []
    for k in np.unique(idx):
        mine = idx == k
        lead.append(float(os_[mine].min() - rs[k]))
        tail.append(float(re[k] - oe[mine].max()))
    return dict(n_ops=len(ops), n_runs=len(runs),
                n_outside=int((outside > 0).sum()),
                max_outside_ns=float(outside.max(initial=0.0)),
                lead_ns=lead, tail_ns=tail)
