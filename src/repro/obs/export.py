"""Chrome-trace-event JSON export (Perfetto / ``chrome://tracing``).

One file carries both layers:

* **pid 1 "harness"** — span tracks (one trace row per ``(track, thread)``
  so B/E pairs nest properly): plan/compile/dispatch/stream/scenario/
  watchdog events, plus compile-server spans merged from the ``xc_worker``
  sidecar (epoch-stamped, rebased onto the tracer's wall-clock anchor).
* **pid 10+k, one per simulated SSD run** (``ssd: <design>``) — the
  flight recorder's reconstruction: per-plane transaction slices
  (``tid = plane``; the scan serializes each plane, so slices never
  overlap within a row), chip-occupancy tracks (``tid = 10000 + node``)
  and, for shared-bus designs, channel-bus tracks
  (``tid = 20000 + row``).  SSD timestamps are ticks converted to
  microseconds (``ticks * TICK_NS / 1e3``).

``validate_trace`` is the schema checker shared by the test suite and the
CI step (``python -m repro.obs.export <file>``): well-formed JSON, finite
non-negative timestamps sorted nondecreasing, every B matched by an E on
its ``(pid, tid)`` in LIFO order, and non-negative X durations.
"""
from __future__ import annotations

import json
import sys

import numpy as np

from repro.obs import events as _events
from repro.ssd.config import TICK_NS

__all__ = ["TraceBuilder", "validate_trace", "main"]

HARNESS_PID = 1
SSD_PID0 = 10
_TID_CHIP = 10_000
_TID_CHAN = 20_000

_US_PER_TICK = TICK_NS / 1e3


class TraceBuilder:
    def __init__(self, max_ssd_events: int = 2_000_000):
        self.events: list[dict] = []
        self.max_ssd_events = max_ssd_events
        self._ssd_pid = SSD_PID0
        self._harness_tids: dict = {}
        self._meta: list[dict] = []

    # ---- low-level emitters --------------------------------------------
    def _name(self, pid: int, tid: int, process: str | None,
              thread: str | None, sort_index: int | None = None) -> None:
        if process is not None:
            self._meta.append({"ph": "M", "pid": pid, "tid": 0,
                               "name": "process_name",
                               "args": {"name": process}})
        if thread is not None:
            self._meta.append({"ph": "M", "pid": pid, "tid": tid,
                               "name": "thread_name",
                               "args": {"name": thread}})
        if sort_index is not None:
            self._meta.append({"ph": "M", "pid": pid, "tid": tid,
                               "name": "thread_sort_index",
                               "args": {"sort_index": sort_index}})

    def _x(self, pid, tid, name, ts, dur, cat, args=None):
        ev = {"ph": "X", "pid": pid, "tid": tid, "name": name,
              "cat": cat, "ts": round(float(ts), 3),
              "dur": round(float(dur), 3)}
        if args:
            ev["args"] = args
        self.events.append(ev)

    def _instant(self, pid, tid, name, ts, cat, args=None):
        ev = {"ph": "i", "pid": pid, "tid": tid, "name": name, "cat": cat,
              "ts": round(float(ts), 3), "s": "t"}
        if args:
            ev["args"] = args
        self.events.append(ev)

    # ---- layer 2: harness spans ----------------------------------------
    def _harness_tid(self, track: str, thread: int) -> int:
        key = (track, thread)
        tid = self._harness_tids.get(key)
        if tid is None:
            tid = len(self._harness_tids) + 1
            self._harness_tids[key] = tid
            nth = sum(1 for (t, _th) in self._harness_tids if t == track)
            label = track if nth == 1 else f"{track} #{nth}"
            self._name(HARNESS_PID, tid, None, label, sort_index=tid)
        return tid

    def add_harness_spans(self, spans: list) -> None:
        """``SpanTracer.drain()`` output -> B/E pairs + instants.

        A sub-resolution span (duration rounds to 0 at µs.3) becomes an X
        event — its E would otherwise sort before its own B at the shared
        timestamp.  The per-pair ``seq`` tiebreaker pairs identical-bounds
        nested spans LIFO: Bs in emission order, their Es in reverse."""
        self._name(HARNESS_PID, 0, "harness", None)
        for seq, (kind, track, name, ts, dur, args, thread) in \
                enumerate(spans):
            tid = self._harness_tid(track, thread)
            if kind == "instant":
                self._instant(HARNESS_PID, tid, name, ts, "harness", args)
            elif round(float(ts + dur), 3) <= round(float(ts), 3):
                self._x(HARNESS_PID, tid, name, ts, 0.0, "harness", args)
            else:
                ev_b = {"ph": "B", "pid": HARNESS_PID, "tid": tid,
                        "name": name, "cat": "harness",
                        "ts": round(float(ts), 3), "_k": (1, -dur, seq)}
                if args:
                    ev_b["args"] = args
                self.events.append(ev_b)
                self.events.append({"ph": "E", "pid": HARNESS_PID,
                                    "tid": tid, "name": name,
                                    "cat": "harness",
                                    "ts": round(float(ts + dur), 3),
                                    "_k": (0, dur, -seq)})

    def add_xc_sidecar(self, path: str, t0_wall: float) -> int:
        """Merge the compile server's epoch-stamped span log (JSON lines
        ``{"name", "t0_epoch", "dur_s", ...extras}``) onto an
        ``xc_worker`` track; returns the number of spans merged."""
        try:
            with open(path) as fh:
                lines = fh.readlines()
        except OSError:
            return 0
        n = 0
        for line in lines:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
                ts = (float(rec["t0_epoch"]) - t0_wall) * 1e6
                dur = float(rec["dur_s"]) * 1e6
            except (ValueError, KeyError, TypeError):
                continue
            tid = self._harness_tid("xc_worker", -1)
            args = {k: v for k, v in rec.items()
                    if k not in ("t0_epoch", "dur_s")}
            self._x(HARNESS_PID, tid, rec.get("name", "compile"),
                    max(ts, 0.0), dur, "xc_worker", args or None)
            n += 1
        return n

    # ---- layer 1: SSD runs ------------------------------------------
    def add_ssd_run(self, run: dict) -> None:
        """One finalized flight-recorder run -> transaction + occupancy
        tracks (see ``events.derive_timeline`` for the reconstruction)."""
        pid = self._ssd_pid
        self._ssd_pid += 1
        label = f"ssd: {run['design']}"
        if run["label"]:
            label += f" [{run['label']}]"
        self._name(pid, 0, label, None)
        n = run["n"]
        if n == 0:
            return
        if n > self.max_ssd_events:
            self._instant(pid, 1, "run_dropped", 0.0, "meta",
                          {"n_txns": int(n)})
            return
        tl = _events.derive_timeline(run)
        comp = run["completion"]
        t0 = tl["t0"]
        kind_name = np.array(["read", "write", "erase"])
        knames = kind_name[np.minimum(run["kind"], 2)]
        failed = run["failed"]

        planes = np.unique(run["plane"])
        for p in planes:
            self._name(pid, int(p) + 1, None, f"plane {int(p)}",
                       sort_index=int(p) + 1)
        phase_items = list(tl["phases"].items())
        for i in range(n):
            args = {
                "arrival_us": round(run["arrival"][i] * _US_PER_TICK, 3),
                "queue_us": round(int(tl["queue"][i]) * _US_PER_TICK, 3),
                "wait_us": round(int(run["wait"][i]) * _US_PER_TICK, 3),
                "conflict": bool(run["conflict"][i]),
                "hops": int(run["hops"][i]),
                "tries": int(run["tries"][i]),
                "chip": int(run["node"][i]),
                "chan": int(run["row"][i]),
            }
            for pname, arr in phase_items:
                args[f"{pname}_us"] = round(int(arr[i]) * _US_PER_TICK, 3)
            name = str(knames[i])
            if failed[i]:
                name = "FAILED " + name
                args["timeout_us"] = round(
                    _events.FAIL_TIMEOUT * _US_PER_TICK, 3)
            self._x(pid, int(run["plane"][i]) + 1, name,
                    t0[i] * _US_PER_TICK,
                    max(int(comp[i] - t0[i]), 0) * _US_PER_TICK,
                    "txn", args)

        chips = np.unique(run["node"])
        for c in chips:
            self._name(pid, _TID_CHIP + int(c), None, f"chip {int(c)}",
                       sort_index=_TID_CHIP + int(c))
        count_bus = run["scalars"]["count_bus"]
        if count_bus:
            for r in np.unique(run["row"]):
                self._name(pid, _TID_CHAN + int(r), None,
                           f"chan {int(r)} (bus)",
                           sort_index=_TID_CHAN + int(r))
        for s, e, mask in tl["occ"]:
            idx = np.flatnonzero(mask & (e > s))
            for i in idx:
                ts = s[i] * _US_PER_TICK
                dur = int(e[i] - s[i]) * _US_PER_TICK
                self._x(pid, _TID_CHIP + int(run["node"][i]), "xfer",
                        ts, dur, "occ")
                if count_bus:
                    self._x(pid, _TID_CHAN + int(run["row"][i]), "xfer",
                            ts, dur, "occ")

        for marker in run["faults"]:
            t_us = marker["t_tick"] * _US_PER_TICK
            for c in marker["dead_chips"]:
                self._instant(pid, _TID_CHIP + int(c), "DEAD", t_us,
                              "fault", {"t_tick": marker["t_tick"]})
            if marker["n_dead_other"] or not marker["dead_chips"]:
                self._instant(pid, 1, "fault_arrival", t_us, "fault",
                              {"dead_chips": len(marker["dead_chips"]),
                               "dead_links_fcs": marker["n_dead_other"]})

    # ---- output ---------------------------------------------------------
    def write(self, path: str) -> dict:
        recorder = _events.RECORDER
        meta = {"tick_ns": TICK_NS}
        if recorder is not None and recorder.dropped_runs:
            meta["dropped_runs"] = recorder.dropped_runs
            meta["dropped_txns"] = recorder.dropped_txns
        # secondary key breaks same-timestamp ties: E before B (a span
        # ending exactly where another begins closes first), inner E
        # (smaller dur) before outer E, outer B (larger dur) before inner
        # B — keeps every (pid, tid) stack LIFO-consistent post-sort
        ordered = sorted(self.events,
                         key=lambda e: (e["ts"], e.get("_k", (1, 0.0))))
        for ev in ordered:
            ev.pop("_k", None)
        events = self._meta + ordered
        doc = {"traceEvents": events, "displayTimeUnit": "ms",
               "otherData": meta}
        with open(path, "w") as fh:
            json.dump(doc, fh, separators=(",", ":"))
        return {
            "path": path,
            "n_events": len(events),
            "n_txn": sum(1 for e in self.events if e.get("cat") == "txn"),
            "n_ssd_pids": self._ssd_pid - SSD_PID0,
            "n_harness_tracks": len(self._harness_tids),
        }


def validate_trace(path_or_doc) -> dict:
    """Schema-validate a trace file (or parsed doc); raises ValueError on
    the first violation, returns a summary dict on success."""
    if isinstance(path_or_doc, (str, bytes)):
        with open(path_or_doc) as fh:
            doc = json.load(fh)
    else:
        doc = path_or_doc
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        raise ValueError("trace: missing traceEvents")
    events = doc["traceEvents"]
    if not isinstance(events, list) or not events:
        raise ValueError("trace: traceEvents empty")
    stacks: dict = {}
    last_ts = None
    counts = {"X": 0, "B": 0, "E": 0, "i": 0, "M": 0}
    n_txn = 0
    pids = set()
    for k, ev in enumerate(events):
        ph = ev.get("ph")
        if ph not in ("X", "B", "E", "i", "M"):
            raise ValueError(f"trace[{k}]: unknown ph {ph!r}")
        counts[ph] += 1
        if ph == "M":
            continue
        ts = ev.get("ts")
        if not isinstance(ts, (int, float)) or not np.isfinite(ts) or ts < 0:
            raise ValueError(f"trace[{k}]: bad ts {ts!r}")
        if last_ts is not None and ts < last_ts:
            raise ValueError(
                f"trace[{k}]: ts not monotonic ({ts} < {last_ts})")
        last_ts = ts
        pids.add(ev.get("pid"))
        key = (ev.get("pid"), ev.get("tid"))
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                raise ValueError(f"trace[{k}]: bad X dur {dur!r}")
            if ev.get("cat") == "txn":
                n_txn += 1
        elif ph == "B":
            stacks.setdefault(key, []).append(ev.get("name"))
        elif ph == "E":
            stack = stacks.get(key)
            if not stack:
                raise ValueError(f"trace[{k}]: E without B on {key}")
            top = stack.pop()
            if ev.get("name") not in (None, top):
                raise ValueError(
                    f"trace[{k}]: E {ev.get('name')!r} closes B {top!r}")
    open_spans = {k: v for k, v in stacks.items() if v}
    if open_spans:
        raise ValueError(f"trace: unclosed B spans on {open_spans}")
    return {"n_events": len(events), "n_txn": n_txn, "counts": counts,
            "n_pids": len(pids)}


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if not argv:
        print("usage: python -m repro.obs.export TRACE.json", file=sys.stderr)
        return 2
    try:
        summary = validate_trace(argv[0])
    except (ValueError, OSError, json.JSONDecodeError) as e:
        print(f"INVALID trace {argv[0]}: {e}", file=sys.stderr)
        return 1
    print(f"OK {argv[0]}: {summary}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
