"""Reduction of a JAX profiler trace of device ops to device busy time,
kernel time and the idle gaps.

``load`` reads the newest ``*.xplane.pb`` under a trace directory (or one
file, gzipped or not) into plain intervals: per device plane
(``/device:<TPU|GPU>:<n>``), the events of its ``XLA Ops`` line, the leaf
operations that ran on the chip.

``reduce`` takes the traced window's length from the caller (the host
clock from the trace's start to its stop: the trace records no host
events, see ``run.BoundedTrace``) and gives:

* ``busy_s``: the union of device-op intervals, averaged over the devices;
  ``window_s``: the window's length;
* ``kernel_s``: per kernel of ``kernel_names.json``, the summed device
  time (over all devices) of its ops: Mosaic custom calls whose HLO name
  starts with one of the kernel's prefixes;
* ``device_ops``: the ten ops that took most device time, per device;
* ``idle_gaps``: the ten largest kinds of idle time, per device: the time
  before the first and after the last device op (the host preparing the
  sweep and reading it back), each gap between ops named by the op that
  ended before it, and gaps under ``SHORT_GAP_NS`` summed under one name
  of their own (the chip's own turnaround, not the host's).

Timestamps are nanoseconds on the profiler's one clock.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
TOP = 10
SHORT_GAP_NS = 10_000
SHORT_GAP = "gaps under 10 us between device ops"
EDGES = "before the first and after the last device op"


def kernel_patterns(path: str | None = None) -> dict:
    """``{"target": <text every kernel op holds>, "kernels": {kernel:
    [HLO name prefixes]}}``."""
    path = path or os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "kernel_names.json")
    with open(path) as f:
        spec = json.load(f)
    return dict(target=spec["target"], kernels=spec["kernels"])


def short_name(op: str) -> str:
    """An op's HLO name: ``%while.395`` of ``%while.395 = (...) while(...)``."""
    return op.split(" = ", 1)[0]


def _xspace_file(path: str) -> str:
    if os.path.isfile(path):
        return path
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb*"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return max(files, key=os.path.getmtime)


def load(path: str) -> dict:
    """``{"devices": {plane: [(name, start_ns, end_ns)]}}``."""
    from jax.profiler import ProfileData

    f = _xspace_file(path)
    with open(f, "rb") as fh:
        raw = fh.read()
    if f.endswith(".gz"):
        raw = gzip.decompress(raw)
    pd = ProfileData.from_serialized_xspace(raw)
    devices = {}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((e.name, float(e.start_ns), float(e.end_ns))
                               for e in line.events)
            devices[plane.name] = sorted(ops, key=lambda o: o[1])
    return dict(devices=devices)


def union(intervals: list) -> list:
    """Merge ``(start, end)`` intervals; returns disjoint sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def reduce(trace: dict, window_s: float, kernels: dict) -> dict:
    if not trace["devices"]:
        raise ValueError("no device plane in the trace")
    target, kernels = kernels["target"], kernels["kernels"]
    n_dev = len(trace["devices"])
    busy, kernel_ns, per_op, idle = [], {k: 0.0 for k in kernels}, {}, {}

    def add_idle(name, ns):
        idle[name] = idle.get(name, 0.0) + ns / n_dev

    for ops in trace["devices"].values():
        ops = sorted(ops, key=lambda o: o[1])
        merged = union([(s, e) for _, s, e in ops])
        busy.append(sum(e - s for s, e in merged))
        end = last = None
        for n, s, e in ops:
            short = short_name(n)
            per_op[short] = per_op.get(short, 0.0) + (e - s)
            if target in n:
                for k, prefixes in kernels.items():
                    if short.startswith(tuple(prefixes)):
                        kernel_ns[k] += e - s
            if end is not None and s > end:
                add_idle(SHORT_GAP if s - end < SHORT_GAP_NS
                         else f"after {last}", s - end)
            if end is None or e > end:
                end, last = e, short
        span = end - ops[0][1] if ops else 0.0
        add_idle(EDGES, max(0.0, window_s * 1e9 - span))
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    top_idle = sorted(idle.items(), key=lambda kv: -kv[1])[:TOP]
    return dict(
        busy_s=sum(busy) / n_dev * 1e-9,
        window_s=window_s,
        n_devices=n_dev,
        kernel_s={k: v * 1e-9 for k, v in kernel_ns.items()},
        device_ops=[[n, v / n_dev * 1e-9] for n, v in top_ops],
        idle_gaps=[[n, v * 1e-9] for n, v in top_idle],
    )


def describe(path: str, top: int = 25) -> str:
    """Planes, lines and the most frequent event names: for looking at one
    trace by hand."""
    from jax.profiler import ProfileData

    f = _xspace_file(path)
    with open(f, "rb") as fh:
        raw = fh.read()
    if f.endswith(".gz"):
        raw = gzip.decompress(raw)
    pd = ProfileData.from_serialized_xspace(raw)
    out = []
    for plane in pd.planes:
        out.append(f"plane {plane.name}")
        for line in plane.lines:
            counts = {}
            for e in line.events:
                c = counts.setdefault(e.name, [0, 0.0])
                c[0] += 1
                c[1] += e.duration_ns
            out.append(f"  line {line.name!r}: {sum(c[0] for c in counts.values())}"
                       " events")
            for n, (c, d) in sorted(counts.items(),
                                    key=lambda kv: -kv[1][1])[:top]:
                out.append(f"    {c:7d} x {d * 1e-6:12.3f} ms  {n[:120]}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    print(describe(sys.argv[1]))
