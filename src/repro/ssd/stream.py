"""Chunked streaming engine: unbounded traces at flat throughput.

The simulator's time base is int32 ticks of 10 ns, so a monolithic replay
caps out at ~21 s of trace (``traces/generator._MAX_SPAN_US``) — far below
the multi-hour MSR-Cambridge volumes the paper evaluates.  This engine
lifts the cap without widening the hot scan state: the trace is cut into
fixed-span *windows*, each window's arrivals are rebased to its own tick
origin (int64 at ingest, int32 inside the window), and every piece of
carried state crosses the boundary explicitly:

* **FTL state** rides the ``resume=`` continuation of
  ``repro.ssd.ftl.decompose_trace``: the carried L2P/free-block/GC state is
  exactly what a monolithic decomposition would hold at the boundary, and
  forcing an allocation-epoch boundary at the window edge is bit-exact
  (epochs are deterministic wear-ordered pops — see
  ``ftl_engine.decompose_vectorized``).
* **In-flight sim state** — per-plane free-at, the one-gap occupancy
  triples of every link/FC/chip/bus, and the scout RNG word — is carried as
  the ``lanec`` executable's scan-state argument (``sim.run_group_carry``)
  and rebased host-side by the window span (``sim.rebase_lane_state``).
  The rebase clamp ``max(t - W, 0)`` is semantics-preserving because window
  arrivals are >= 0: a transaction incomplete at the boundary keeps exactly
  its residual occupancy, so it re-enters the next window with its residual
  latency intact.
* **Commit order** is kept *identical* to the monolithic run: windows are
  cut by arrival for decomposition (FTL causality), but execution batches
  are formed by **nominal commit time** — the per-plane nominal FIFO
  availability is carried into each window's ``sim._nominal_times`` pass,
  and any transaction whose nominal time lands past the window end is
  deferred and re-injected into the next window's batch with its
  (frame-shifted, possibly negative) original arrival, i.e. with its
  residual latency intact.  Batches stable-sorted by nominal with
  decomposition-order ties therefore concatenate to exactly the global
  nominal order, so resources commit in the monolithic sequence even when
  a backlog straddles the cut.

The steady state is **execution-bound**: while window N executes, a
single-worker prep thread decomposes window N+1 and routes any missing
executables through ``sweep_plan``'s background compile pool.  Windows
share one ``lanec`` executable per (geometry, capacity bucket, cost class,
promotions) — the capacity bucket is a running high-water mark — so after
window 1 the per-window compile wait is ~0.

Bit-exactness contract (pinned by ``tests/test_stream.py``): a windowed
replay of any prefix that fits one window is bit-identical to
``sim.simulate`` of that prefix, and window-boundary carry (GC at the
edge, in-flight transactions spanning it, empty interior windows)
reproduces the monolithic run's per-request latencies and completions
exactly.
"""
from __future__ import annotations

import concurrent.futures
import time
from typing import NamedTuple, Sequence

import jax
import numpy as np

from repro.core.topology import build_mesh
from repro.obs import events as obs_events
from repro.obs import spans as obs_spans
from repro.ssd import bench
from repro.ssd import sim as S
from repro.ssd import sweep_plan as SP
from repro.ssd.config import SSDConfig, TICK_NS
from repro.ssd.designs import (
    KIND_SCOUT,
    LaneTables,
    REGISTRY,
    lower_designs,
    resolve_specs,
)
from repro.ssd.ftl import KIND_READ, KIND_WRITE, decompose_trace
from repro.traces.generator import to_pages

__all__ = ["DEFAULT_WINDOW_S", "StreamResult", "stream_simulate",
           "window_ticks_for"]

DEFAULT_WINDOW_S = 10.0
_I32_MAX = 2**31 - 1
# completions of in-flight transactions run past the window end, so the
# window span keeps ~2.7 s of int32 headroom for the overhang
_HEADROOM_TICKS = 1 << 28


def window_ticks_for(window_s: float) -> int:
    """Window span in ticks; guards the int32 scheduling headroom."""
    w = int(round(window_s * 1e9 / TICK_NS))
    if not 0 < w <= _I32_MAX - _HEADROOM_TICKS:
        raise ValueError(
            f"window_s={window_s!r} must be in (0, "
            f"{(_I32_MAX - _HEADROOM_TICKS) * TICK_NS * 1e-9:.1f}] s "
            "(int32 tick budget minus in-flight completion headroom)"
        )
    return w


def _arrival_ticks_abs(arrival_us) -> np.ndarray:
    """Absolute int64 arrival ticks — the exact float64 op sequence of
    ``us_to_ticks`` so window-rebased ticks match a monolithic replay."""
    us = np.asarray(arrival_us, np.float64)
    return np.ceil(us * 1e3 / TICK_NS).astype(np.int64)


class StreamResult(NamedTuple):
    """A windowed replay: per-design results + per-window telemetry."""

    results: list  # SimResult per design (absolute int64 tick frame)
    windows: list  # per-window dicts (n_requests, wall_s, ios_per_wallclock_s, ...)
    window_ticks: int
    n_windows: int
    n_requests: int
    ftl: object  # final carried FTL (state-parity tests)

    def throughput_flatness(self) -> float:
        """last-window / first-steady-window simulated-IOs per wall-clock
        second; 1.0 means perfectly flat.  The first nonempty window is
        warm-up (it pays the one-time executable load / compile wait) and
        is skipped when later nonempty windows exist."""
        tp = [w["ios_per_wallclock_s"] for w in self.windows
              if w["n_requests"]]
        if len(tp) > 2:
            tp = tp[1:]  # drop warm-up
        if len(tp) < 2 or tp[0] <= 0:
            return 1.0
        return tp[-1] / tp[0]


class _Lane:
    """One design's streaming lane: static program identity + carried
    scan state."""

    __slots__ = ("design", "tables_row", "scout", "k_max", "fixed", "state")

    def __init__(self, design, tables_row, scout, k_max, fixed, state):
        self.design = design
        self.tables_row = tables_row
        self.scout = scout
        self.k_max = k_max
        self.fixed = fixed
        self.state = state


def _active_faults(schedule: dict, w: int):
    """Latest scheduled ``FaultSpec`` at or before window ``w`` (windows
    inherit the most recent boundary's spec; None before the first)."""
    spec = None
    for k in sorted(schedule):
        if k <= w:
            spec = schedule[k]
    return spec


def _finish_stream(cfg: SSDConfig, design: str, agg: dict,
                   n_req_total: int, tenant) -> S.SimResult:
    """``sim._finish_result`` over the stream's concatenated (absolute,
    int64) per-transaction arrays — same reductions, widened tick frame."""
    completion = agg["completion"]
    arrival = agg["arrival"]
    latency = completion - arrival
    n = len(completion)
    exec_ticks = int(completion.max() - arrival.min()) if n else 0

    req = agg["req"]
    failed = agg["failed"]
    req_done = np.zeros((n_req_total,), np.int64)
    req_arr = np.full((n_req_total,), np.iinfo(np.int64).max)
    req_fail = np.zeros((n_req_total,), bool)
    host = req >= 0
    np.maximum.at(req_done, req[host], completion[host])
    np.minimum.at(req_arr, req[host], arrival[host])
    np.logical_or.at(req_fail, req[host], failed[host])
    seen = req_arr < np.iinfo(np.int64).max
    req_latency = (req_done - req_arr)[seen]
    req_completion = req_done[seen]
    req_tenant = None
    if tenant is not None and len(tenant) >= n_req_total:
        req_tenant = np.asarray(tenant, np.int32)[:n_req_total][seen]

    pm = cfg.power
    tick_s = TICK_NS * 1e-9
    kind = agg["kind"]
    op = agg["op"]
    die_w = np.where(
        kind == KIND_READ,
        pm.die_read_w,
        np.where(kind == KIND_WRITE, pm.die_prog_w, pm.die_erase_w),
    )
    flash_energy = float(np.sum(op.astype(np.float64) * tick_s * die_w))
    bus_hold = int(agg["bus_hold_ticks"])
    link_hold = int(agg["link_hold_ticks"])
    transfer_energy = (
        bus_hold * tick_s * pm.bus_active_w
        + link_hold * tick_s * pm.link_active_w
    )
    n_routers = REGISTRY[design].n_routers(build_mesh(cfg.rows, cfg.cols))
    static_energy = (pm.static_w + n_routers * pm.router_w) * exec_ticks * tick_s

    return S.SimResult(
        design=design,
        completion=completion,
        latency=latency,
        req_latency=req_latency,
        wait=agg["wait"],
        conflict=agg["conflict"],
        hops=agg["hops"],
        tries=agg["tries"],
        misroutes=agg["misroutes"],
        exec_ticks=exec_ticks,
        bus_hold_ticks=bus_hold,
        link_hold_ticks=link_hold,
        flash_energy_j=flash_energy,
        transfer_energy_j=float(transfer_energy),
        static_energy_j=float(static_energy),
        req_completion=req_completion,
        req_tenant=req_tenant,
        failed=failed,
        req_failed=req_fail[seen],
    )


def _resolve_executable(key: tuple) -> float:
    """Block until ``key``'s executable is loaded; returns the main-thread
    stall seconds (mirrors ``sweep_plan._execute_plans``'s wait pattern —
    an in-flight background compile is adopted, anything else is compiled
    here)."""
    if key in S._EXEC_CACHE:
        return 0.0
    t0 = time.perf_counter()
    fut = SP._INFLIGHT.pop(key, None)
    if fut is not None:
        fut.result()
    else:
        S.ensure_compiled(key)
    return time.perf_counter() - t0


def stream_simulate(
    cfg: SSDConfig,
    trace,
    designs: Sequence[str] = ("venice",),
    seeds: int | Sequence[int] = 0,
    window_s: float = DEFAULT_WINDOW_S,
    engine: str = "auto",
    overprovision: float = 1.28,
    precondition: bool = True,
    decompose_seed: int = 0,
    faults=None,
    fault_schedule: dict | None = None,
    capture: list | None = None,
) -> StreamResult:
    """Replay an arbitrarily long trace in tick-rebased windows.

    ``trace`` is a canonical byte trace (``offset_bytes``/``size_bytes``)
    or an already-paged trace (``offset_page``/``n_pages`` +
    ``footprint_pages``).  Windows are decomposed with the carried FTL,
    ordered with the carried nominal availability, executed with the
    carried scan state, and window N+1's decomposition + compile overlap
    window N's execution on a single prep thread.  Returns a
    :class:`StreamResult` whose per-design :class:`~repro.ssd.sim.SimResult`
    carries absolute int64 ticks.

    ``faults`` (a ``designs.FaultSpec``) injects hardware faults for the
    whole replay; ``fault_schedule`` maps window index -> ``FaultSpec``
    taking effect at that window's START (a window boundary), modelling
    mid-trace fault arrival — later windows inherit the latest boundary's
    spec.  Faulted tables are swapped in as ARGUMENTS of the same
    ``lanec`` executables (promotions are fault-invariant), so a schedule
    never costs a recompile.  Hardware-fault windows stay bit-identical
    to a monolithic ``sim.simulate`` with the same spec; read-retry draws
    are keyed on window-frame arrivals and are therefore stream-frame
    specific.

    ``capture`` (debug hook): a list that receives one dict per window —
    ``{"w", "packed", "n"}`` with the exact window-frame execution batch
    the lanes scanned — so a scalar reference can replay the identical
    per-window batches (``tests/test_faults.py`` pins the mid-stream
    fault-arrival path element-wise this way).
    """
    designs = tuple(designs)
    specs = resolve_specs(designs)
    if isinstance(seeds, (int, np.integer)):
        seeds = (int(seeds),) * len(designs)
    seeds = tuple(int(s) for s in seeds)
    if len(seeds) != len(designs):
        raise ValueError(
            f"got {len(seeds)} seeds for {len(designs)} design lanes"
        )

    pages = trace if "offset_page" in trace else to_pages(trace,
                                                         cfg.page_bytes)
    fp = int(pages["footprint_pages"])
    t_abs = _arrival_ticks_abs(pages["arrival_us"])
    n_requests = len(t_abs)
    if n_requests == 0:
        raise ValueError("cannot stream an empty trace")
    if np.any(np.diff(t_abs) < 0):
        raise ValueError("stream_simulate requires time-ordered arrivals")

    W = window_ticks_for(window_s)
    n_windows = int(t_abs[-1] // W) + 1
    bounds = np.searchsorted(t_abs, np.arange(1, n_windows + 1) * W,
                             side="left")
    starts = np.concatenate(([0], bounds[:-1]))

    schedule = {int(k): v for k, v in (fault_schedule or {}).items()}
    if faults is not None:
        schedule.setdefault(0, faults)
    if any(k < 0 for k in schedule):
        raise ValueError("fault_schedule windows must be >= 0")
    cur_spec = _active_faults(schedule, 0)

    tables = lower_designs(cfg, designs, cur_spec)
    sig = S._geom_sig(cfg)
    lanes = []
    for i, spec in enumerate(specs):
        tables_row = LaneTables(*(np.asarray(a)[i] for a in tables))
        scout = spec.kind == KIND_SCOUT
        k_max = spec.n_scouts if scout else 1
        fixed = S._promotions(tables_row)
        state = S.initial_lane_state(cfg, scout, seeds[i] | 1)
        lanes.append(_Lane(designs[i], tables_row, scout, k_max, fixed,
                           state))

    perf = bench.PERF
    c0 = perf.get("compile_s", 0.0)
    _POOL_FIELDS = ("arrival", "kind", "plane", "node", "row", "nbytes",
                    "req", "nominal")
    carry = {
        "ftl": None,
        "nom_avail": np.zeros((cfg.n_planes,), np.int64),
        "cap": 0,
        "req_base": 0,
        # deferred transactions: decomposed in an earlier window but
        # nominally committing in a later one, kept in global decomposition
        # order with frame-rebased (possibly negative) arrivals/nominals
        "pool": None,
    }

    def _prepare(w: int) -> dict:
        """Decompose, defer-partition, order, and pack window ``w``'s
        execution batch, then schedule its compiles.

        Runs on the prep thread for w > 0 (overlapped with window w-1's
        execution); mutates ``carry`` — safe because preps execute strictly
        in sequence on the single worker.

        The batch is formed by *nominal commit time*, not arrival: window
        ``w`` executes every pending transaction whose nominal time lands
        before the window end, and defers the rest — re-injected next
        window with arrival/nominal shifted into that frame.  Stable-sorted
        by nominal with ties falling back to decomposition order (the pool
        is kept in global order), the concatenation of per-window batches
        IS the monolithic nominal order, which is what makes boundary
        carry bit-exact even when a backlog straddles the cut."""
        t0 = time.perf_counter()
        lo, hi = int(starts[w]), int(bounds[w])
        sl = slice(lo, hi)
        win = {
            "arrival_us": np.asarray(pages["arrival_us"])[sl],
            "is_read": np.asarray(pages["is_read"])[sl],
            "offset_page": np.asarray(pages["offset_page"])[sl],
            "n_pages": np.asarray(pages["n_pages"])[sl],
            "footprint_pages": fp,
        }
        txns = decompose_trace(
            cfg, win, footprint_pages=fp, overprovision=overprovision,
            precondition=(precondition and carry["ftl"] is None),
            seed=decompose_seed, engine=engine, resume=carry["ftl"],
            arrival_ticks=t_abs[sl] - w * W,
        )
        carry["ftl"] = txns.ftl
        nominal, avail_out = S._nominal_times(cfg, txns, carry["nom_avail"])
        carry["nom_avail"] = np.maximum(avail_out - W, 0)
        req = np.asarray(txns["req"], np.int64)
        new = {f: np.asarray(txns[f], np.int64) for f in _POOL_FIELDS[:-2]}
        new["req"] = np.where(req >= 0, req + carry["req_base"], -1)
        new["nominal"] = nominal
        carry["req_base"] += hi - lo
        pool = (new if carry["pool"] is None else
                {f: np.concatenate((carry["pool"][f], new[f]))
                 for f in _POOL_FIELDS})
        # the last window flushes everything still pending
        take = (np.ones(len(pool["nominal"]), bool) if w == n_windows - 1
                else pool["nominal"] < W)
        batch = {f: pool[f][take] for f in _POOL_FIELDS}
        if take.all():
            carry["pool"] = None
        else:
            defer = {f: pool[f][~take] for f in _POOL_FIELDS}
            defer["arrival"] = defer["arrival"] - W
            defer["nominal"] = defer["nominal"] - W
            if int(defer["arrival"].min()) <= S.REBASE_FLOOR:
                raise ValueError(
                    "streamed backlog: transactions deferred so far past "
                    "their window that rebased arrivals fall below the "
                    f"int32 rebase floor; increase window_s (={window_s}) "
                    "or reduce the offered load"
                )
            carry["pool"] = defer
        order = np.argsort(batch["nominal"], kind="stable")
        packed, op = S._pack_txns(cfg, batch, order,
                                  _active_faults(schedule, w))
        n = len(order)
        cap = max(carry["cap"], S._pad_to(max(n, 1)))
        carry["cap"] = cap
        prep = {
            "w": w, "n": n, "n_req": hi - lo, "cap": cap,
            "packed": packed, "op": op,
            "padded": SP._pad_txns(packed, cap) if n else None,
            "req": batch["req"][order],
            "arrival_abs": batch["arrival"][order] + w * W,
            "keys": [],
        }
        if n:
            prep["keys"] = [
                S.lanec_group_key(sig, cap, 1, ln.k_max, ln.scout,
                                  ln.fixed, 1)
                for ln in lanes
            ]
            SP._schedule_compiles(list(dict.fromkeys(prep["keys"])))
        prep["prep_s"] = time.perf_counter() - t0
        perf["stream_prep_s"] = (perf.get("stream_prep_s", 0.0)
                                 + prep["prep_s"])
        return prep

    def _prep_traced(w: int) -> dict:
        with obs_spans.span("stream-prep", "prep", window=w):
            return _prepare(w)

    agg = [
        {"completion": [], "arrival": [], "wait": [], "conflict": [],
         "hops": [], "tries": [], "misroutes": [], "kind": [], "op": [],
         "req": [], "failed": [], "bus_hold_ticks": 0,
         "link_hold_ticks": 0}
        for _ in designs
    ]
    windows: list = []
    wait_total = 0.0

    rec = obs_events.RECORDER
    stream_id = rec.stream_token() if rec is not None else 0
    if rec is not None and cur_spec is not None:
        for ln in lanes:
            rec.record_fault_swap(ln.design, 0, ln.tables_row,
                                  cfg.rows * cfg.cols, stream_id)
    tracer = obs_spans.TRACER
    pool = concurrent.futures.ThreadPoolExecutor(
        max_workers=1, thread_name_prefix="stream-prep")
    try:
        prep = _prep_traced(0)
        fut_next = (pool.submit(_prep_traced, 1) if n_windows > 1 else None)
        for w in range(n_windows):
            t_w = time.perf_counter()
            t_span = tracer.now_us() if tracer is not None else 0.0
            base = w * W
            # window-boundary fault injection: swap the faulted tables in
            # as executable ARGUMENTS (the lanec key's promotions are
            # fault-invariant), carrying the scan state across untouched —
            # in-flight occupancy survives the fault arrival, exactly as
            # a mid-trace failure would leave it
            spec_w = _active_faults(schedule, w)
            if spec_w is not cur_spec:
                cur_spec = spec_w
                t_f = lower_designs(cfg, designs, cur_spec)
                for i, ln in enumerate(lanes):
                    ln.tables_row = LaneTables(
                        *(np.asarray(a)[i] for a in t_f))
                if rec is not None:
                    for ln in lanes:
                        rec.record_fault_swap(ln.design, base,
                                              ln.tables_row,
                                              cfg.rows * cfg.cols,
                                              stream_id)
                obs_spans.instant("stream", "fault_swap", window=w)
            n = prep["n"]
            if capture is not None:
                capture.append({"w": w, "packed": prep["packed"], "n": n})
            exec_s = 0.0
            wait_s = 0.0
            if n:
                n_chunks = np.asarray([-(-n // S.CHUNK)], np.int32)
                txns_g = S.TxnArrays(*(a[None] for a in prep["padded"]))
                for i, ln in enumerate(lanes):
                    wait_s += _resolve_executable(prep["keys"][i])
                    tables_g = LaneTables(
                        *(np.asarray(getattr(ln.tables_row, f))[None]
                          for f in LaneTables._fields)
                    )
                    state_g = jax.tree_util.tree_map(
                        lambda a: np.asarray(a)[None], ln.state)
                    st, outs, g = S.run_group_carry(
                        sig, tables_g, state_g, txns_g, n_chunks,
                        ln.k_max, ln.scout, ln.fixed, 1,
                    )
                    ln.state = jax.tree_util.tree_map(
                        lambda a: np.asarray(a)[0], st)
                    out_row = S.StepOut(
                        *(np.asarray(a)[0][:n] for a in outs))
                    if rec is not None:
                        rec.record_window(
                            cfg, ln.design, prep["packed"], prep["op"],
                            out_row, base, n, prep["arrival_abs"],
                            ln.tables_row, ln.scout, stream_id,
                        )
                    a = agg[i]
                    a["completion"].append(
                        out_row.completion.astype(np.int64) + base)
                    a["arrival"].append(prep["arrival_abs"])
                    a["wait"].append(out_row.wait)
                    a["conflict"].append(out_row.conflict)
                    a["hops"].append(out_row.hops)
                    a["tries"].append(out_row.tries)
                    a["misroutes"].append(out_row.misroutes)
                    a["kind"].append(np.asarray(prep["packed"].kind))
                    a["op"].append(prep["op"])
                    a["req"].append(prep["req"])
                    a["failed"].append(out_row.failed)
                    a["bus_hold_ticks"] += int(
                        out_row.bus_hold.astype(np.int64).sum())
                    a["link_hold_ticks"] += int(
                        out_row.link_hold.astype(np.int64).sum())
                    exec_s += g["exec_s"]
                    g["window"] = w
                    perf["lanes"] = perf.get("lanes", 0) + 1
                    perf["scan_steps_padded"] = (
                        perf.get("scan_steps_padded", 0) + g["steps"])
                    perf["scan_steps_valid"] = (
                        perf.get("scan_steps_valid", 0) + n)
                    perf["exec_s"] = perf.get("exec_s", 0.0) + g["exec_s"]
                    perf.setdefault("groups", []).append(g)
                perf["devices_used"] = max(perf.get("devices_used", 0), 1)
            # every lane's clock rolls forward by one window span, txns
            # or not — an idle window still ages the carried occupancy
            for ln in lanes:
                ln.state = S.rebase_lane_state(ln.state, W)
            wait_total += wait_s
            wall_s = time.perf_counter() - t_w
            if tracer is not None:
                tracer.complete("stream", f"window {w}", t_span,
                                tracer.now_us() - t_span,
                                {"n_txns": n, "n_requests": prep["n_req"],
                                 "compile_wait_s": round(wait_s, 4)})
            windows.append({
                "window": w,
                "n_requests": prep["n_req"],
                "n_txns": n,
                "prep_s": round(prep["prep_s"], 4),
                "exec_s": round(exec_s, 4),
                "compile_wait_s": round(wait_s, 4),
                "wall_s": round(wall_s, 4),
                "ios_per_wallclock_s": round(
                    prep["n_req"] / max(wall_s, 1e-9), 2),
            })
            if fut_next is not None:
                prep = fut_next.result()
                fut_next = (pool.submit(_prep_traced, w + 2)
                            if w + 2 < n_windows else None)
    finally:
        pool.shutdown(wait=True)

    perf["compile_wait_s"] = perf.get("compile_wait_s", 0.0) + wait_total
    perf["compile_overlap_s"] = perf.get("compile_overlap_s", 0.0) + max(
        0.0, (perf.get("compile_s", 0.0) - c0) - wait_total)
    perf["stream_windows"] = perf.get("stream_windows", 0) + n_windows

    tenant = pages.get("tenant")
    cat = lambda chunks, dt: (np.concatenate(chunks).astype(dt) if chunks
                              else np.zeros(0, dt))
    results = []
    for i, ln in enumerate(lanes):
        a = agg[i]
        results.append(_finish_stream(cfg, ln.design, {
            "completion": cat(a["completion"], np.int64),
            "arrival": cat(a["arrival"], np.int64),
            "wait": cat(a["wait"], np.int32),
            "conflict": cat(a["conflict"], bool),
            "hops": cat(a["hops"], np.int32),
            "tries": cat(a["tries"], np.int32),
            "misroutes": cat(a["misroutes"], np.int32),
            "kind": cat(a["kind"], np.int32),
            "op": cat(a["op"], np.int32),
            "req": cat(a["req"], np.int64),
            "failed": cat(a["failed"], bool),
            "bus_hold_ticks": a["bus_hold_ticks"],
            "link_hold_ticks": a["link_hold_ticks"],
        }, n_requests, tenant))
    return StreamResult(
        results=results,
        windows=windows,
        window_ticks=W,
        n_windows=n_windows,
        n_requests=n_requests,
        ftl=carry["ftl"],
    )
