"""Benchmark harness — one function per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run             # quick preset
  PYTHONPATH=src python -m benchmarks.run --full      # all 19+6 workloads
  PYTHONPATH=src python -m benchmarks.run --smoke     # CI probe: 1 wl x 2 designs
  PYTHONPATH=src python -m benchmarks.run --only fig9 --csv results/
  PYTHONPATH=src python -m benchmarks.run --designs venice,venice_kscout,ideal
  PYTHONPATH=src python -m benchmarks.run --json results/BENCH_quick.json
  PYTHONPATH=src python -m benchmarks.run --ftl-engine scalar   # FTL A/B

Every sweep phase runs all requested designs through ONE compiled batched
program (``repro.ssd.sim.simulate_sweep``); ``--json`` records the perf
trajectory as a ``BENCH_*.json`` artifact so regressions are visible across
commits: per-phase wall-clock is split into ``ftl_s`` (trace → transaction
decomposition — the array-native engine, or the scalar oracle under
``--ftl-engine scalar``) and ``sim_s`` (the jitted sweep), plus per-design
speedups and cache telemetry.

Figures reproduced (as CSV tables; all values also summarized to stdout):
  fig4    prior approaches + ideal vs Baseline (perf-optimized)
  fig9    speedups, all designs x {perf, cost} configs
  fig10   IOPS normalized to the conflict-free ideal
  fig11   p99 tail latency (src1_0, hm_0)
  fig12   mixed workloads (Table 3)
  fig13   % requests experiencing path conflicts
  fig14   power / energy normalized to Baseline
  fig15   sensitivity: 4x16 / 8x8 / 16x4 flash-controller configs
  tab4    router/link power & area overheads (analytic)
  sec31   the two-read service-time example (exact latencies)
  tail    beyond-figures QoS surface (workloads subsystem): closed-loop
          queue-depth sweeps (synthetic + bundled real-trace fixture) and
          multi-tenant fairness — per-design p50/p95/p99 into BENCH_*.json
  stream  chunked streaming engine: a ~90 s (beyond the int32 tick budget)
          trace replayed in 10 s windows — per-window IO/s into
          BENCH_*.json; acceptance is flat throughput across windows

Every figure phase hands its whole (workload, config) list to the sweep
planner (``repro.ssd.sweep_plan.prefetch``) before its body runs, so the
phase's sweeps execute as lane groups sharded across the host CPU devices
(one virtual XLA device per core, forced below *before* jax initializes)
instead of one eager sweep per workload.
"""
from __future__ import annotations

import os

# One XLA host device per core so the sweep planner can shard lane groups,
# and JAX's persistent compile cache placed (see repro.xla_env).  MUST run
# before any jax import: jax reads these on first init.
from repro.xla_env import configure as _configure_xla

_configure_xla()

import argparse
import csv
import json
import time

import numpy as np

from repro.ssd import DESIGNS as ALL_DESIGNS
from repro.ssd import bench, cost_optimized, perf_optimized
from repro.ssd import sim
from repro.ssd import sweep_plan
from repro.ssd.bench import geomean, run_workload
from repro.ssd.sweep_plan import (
    RunRequest,
    precompile,
    prefetch,
    prewarm_small_keys,
)
from repro.traces import MIXES, WORKLOADS

QUICK_WL = ["proj_3", "src2_1", "hm_0", "prxy_0", "YCSB_B", "ssd-10", "usr_0"]
DEFAULT_DESIGNS = ("baseline", "pssd", "pnssd", "nossd", "venice", "ideal")
N_REQ_QUICK = 2500
# CI probe: the smallest run that still exercises the whole pipeline —
# trace gen -> FTL -> both cost classes (bus-routed baseline + scout-routed
# venice) -> metrics/CSV/JSON.  Keeps the fast lane failing on pipeline
# regressions without paying for a full sweep.
SMOKE_WL = ["hm_0"]
SMOKE_DESIGNS = ("baseline", "venice")
N_REQ_SMOKE = 240
SMOKE_PHASES = ("fig4_9_10_13", "tail", "stream", "faults", "tab4", "sec31")

# bundled anonymized MSR-format trace (tests/data, <50 KB): the real-trace
# leg of the tail phase and the ingestion tests share this fixture
FIXTURE_TRACE = os.path.join(
    os.path.dirname(__file__), "..", "tests", "data", "msr_sample.csv"
)


def _rows_to_csv(path, header, rows):
    if path:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w", newline="") as f:
            w = csv.writer(f)
            w.writerow(header)
            w.writerows(rows)


def _runs(workloads, cfg, n_req, designs, seed=0):
    out = {}
    for wl in workloads:
        t0 = time.time()
        out[wl] = run_workload(wl, cfg, designs=designs, n_requests=n_req,
                               seed=seed)
        print(f"  [{cfg.name}] {wl}: {time.time()-t0:.0f}s", flush=True)
    return out


def fig4_and_9_and_10_and_13(workloads, n_req, csv_dir, designs):
    rows9, rows10, rows13 = [], [], []
    summary = {}
    has_ideal = "ideal" in designs  # fig10 normalizes IOPS to the ideal lane
    cfgs = (perf_optimized(), cost_optimized())
    # one planning pass over BOTH configs: perf/cost share a geometry, so
    # their lanes pool into the same sharded groups
    prefetch([RunRequest(wl, cfg, designs, n_req)
              for cfg in cfgs for wl in workloads])
    for cfg in cfgs:
        runs = _runs(workloads, cfg, n_req, designs)
        sp = {d: [] for d in designs}
        for wl, r in runs.items():
            for d in designs:
                s = r.speedup(d)
                sp[d].append(s)
                rows9.append([cfg.name, wl, d, f"{s:.3f}"])
                if has_ideal:
                    rows10.append([cfg.name, wl, d, f"{r.iops_norm(d):.3f}"])
                rows13.append(
                    [cfg.name, wl, d,
                     f"{r.results[d].conflict_rate()*100:.2f}"]
                )
        summary[cfg.name] = {d: geomean(sp[d]) for d in designs}
        print(f"[fig9/{cfg.name}] geomean speedups: "
              + " ".join(f"{d}={summary[cfg.name][d]:.2f}x" for d in designs))
    _rows_to_csv(os.path.join(csv_dir, "fig9_speedup.csv"),
                 ["config", "workload", "design", "speedup"], rows9)
    if has_ideal:
        _rows_to_csv(os.path.join(csv_dir, "fig10_iops.csv"),
                     ["config", "workload", "design", "iops_norm_ideal"],
                     rows10)
    else:
        print("[fig10] skipped: no 'ideal' lane to normalize against")
    _rows_to_csv(os.path.join(csv_dir, "fig13_conflicts.csv"),
                 ["config", "workload", "design", "conflict_pct"], rows13)
    return summary


# phase request shapes shared with the cross-phase precompile in main()
FIG11_WLS = ("src1_0", "hm_0")
FIG15_MESHES = ((4, 16), (8, 8), (16, 4))
FIG15_WLS = ("proj_3", "src2_1", "YCSB_B")


def fig11_tail_latency(n_req, csv_dir, designs):
    cfg = perf_optimized()
    rows = []
    wls = FIG11_WLS
    prefetch([RunRequest(wl, cfg, designs, n_req) for wl in wls])
    for wl in wls:
        r = run_workload(wl, cfg, designs=designs, n_requests=n_req)
        for d in designs:
            p99 = r.results[d].p99_latency_us()
            rows.append([wl, d, f"{p99:.1f}"])
            print(f"[fig11] {wl} {d}: p99={p99:.1f}us")
    _rows_to_csv(os.path.join(csv_dir, "fig11_p99.csv"),
                 ["workload", "design", "p99_latency_us"], rows)


def fig12_mixes(n_req, csv_dir, designs, mixes=None):
    cfg = perf_optimized()
    rows = []
    gm = {d: [] for d in designs}
    mixes = tuple(mixes or sorted(MIXES))
    prefetch([RunRequest(mix, cfg, designs, n_req) for mix in mixes])
    for mix in mixes:
        r = run_workload(mix, cfg, designs=designs, n_requests=n_req)
        for d in designs:
            s = r.speedup(d)
            gm[d].append(s)
            rows.append([mix, d, f"{s:.3f}"])
    print("[fig12] mixes geomean: "
          + " ".join(f"{d}={geomean(gm[d]):.2f}x" for d in designs))
    _rows_to_csv(os.path.join(csv_dir, "fig12_mixes.csv"),
                 ["mix", "design", "speedup"], rows)


def fig14_power_energy(workloads, n_req, csv_dir, designs):
    cfg = perf_optimized()
    rows = []
    agg = {d: ([], []) for d in designs}
    prefetch([RunRequest(wl, cfg, designs, n_req) for wl in workloads])
    for wl in workloads:
        r = run_workload(wl, cfg, designs=designs, n_requests=n_req)
        base = r.results["baseline"]
        for d in designs:
            p = r.results[d].avg_power_w / base.avg_power_w
            e = r.results[d].energy_j / base.energy_j
            agg[d][0].append(p)
            agg[d][1].append(e)
            rows.append([wl, d, f"{p:.3f}", f"{e:.3f}"])
    for d in designs:
        print(f"[fig14] {d}: power={np.mean(agg[d][0]):.3f}x "
              f"energy={np.mean(agg[d][1]):.3f}x of baseline")
    _rows_to_csv(os.path.join(csv_dir, "fig14_power_energy.csv"),
                 ["workload", "design", "power_norm", "energy_norm"], rows)


def fig15_sensitivity(n_req, csv_dir, designs):
    rows = []
    designs = tuple(d for d in designs if d != "pnssd")  # needs rows==cols
    meshes = FIG15_MESHES
    wls = FIG15_WLS
    prefetch([RunRequest(wl, perf_optimized(rows=r_, cols=c_), designs, n_req)
              for (r_, c_) in meshes for wl in wls])
    for (r_, c_) in meshes:
        cfg = perf_optimized(rows=r_, cols=c_)
        gm = {d: [] for d in designs}
        for wl in wls:
            run = run_workload(wl, cfg, designs=designs, n_requests=n_req)
            for d in designs:
                gm[d].append(run.speedup(d))
        print(f"[fig15] {r_}x{c_}: " + " ".join(
            f"{d}={geomean(gm[d]):.2f}x" for d in designs))
        for d in designs:
            rows.append([f"{r_}x{c_}", d, f"{geomean(gm[d]):.3f}"])
    _rows_to_csv(os.path.join(csv_dir, "fig15_sensitivity.csv"),
                 ["mesh", "design", "geomean_speedup"], rows)


def tail_qos(n_req, csv_dir, designs, smoke=False):
    """QoS surface (workloads subsystem): closed-loop queue-depth sweeps on
    a synthetic workload AND the bundled real-trace fixture, plus a
    multi-tenant fairness scenario — per-design p50/p95/p99 + per-tenant
    slowdown/fairness, exported under the ``tail`` key of BENCH_*.json."""
    from repro.workloads import ingest_file
    from repro.workloads.scenario import (
        MultiTenantMix,
        QueueDepthSweep,
        run_queue_depth_sweeps,
        run_scenario,
    )

    cfg = perf_optimized()
    fixture = ingest_file(FIXTURE_TRACE, name="msr_fixture")
    qds = (1, 8, 64) if smoke else (1, 4, 16, 64)
    iters = 3 if smoke else 6  # feedback rounds (see QueueDepthSweep doc)
    qd_scns = [QueueDepthSweep(fixture, qds=qds, iters=iters,
                               n_requests=(240 if smoke else None))]
    if not smoke:  # the synthetic leg of the QD acceptance sweep:
        # read-heavy proj_3 — writes bury the depth response under
        # GC/tPROG plane time, reads expose the channel-conflict queueing
        qd_scns.insert(0, QueueDepthSweep("proj_3", qds=qds, iters=iters,
                                          n_requests=800))
    # the QD sweeps iterate ROUND-MERGED (one planner batch per feedback
    # round across all sweeps — bit-identical, but the dispatch-bound
    # tail collapses into full small-lane groups; see scenario.py)
    records = list(run_queue_depth_sweeps(cfg, qd_scns, designs))
    records.append(run_scenario(
        cfg, MultiTenantMix(("mix1",),
                            n_requests_each=(120 if smoke else 400)),
        designs,
    ))
    rows_qd, rows_fair = [], []
    for rec in records:
        if rec["scenario"] == "queue_depth_sweep":
            for d, per in rec["designs"].items():
                for q, m in per.items():
                    rows_qd.append([rec["workload"], d, q, m["p50_us"],
                                    m["p95_us"], m["p99_us"], m["iops"]])
            p99 = {d: per[str(qds[-1])]["p99_us"]
                   for d, per in rec["designs"].items()}
            print(f"[tail] {rec['workload']} QD{qds[-1]} p99: "
                  + " ".join(f"{d}={v:.0f}us" for d, v in p99.items()))
        else:
            for d, m in rec["designs"].items():
                for t, tm in m.get("tenants", {}).items():
                    rows_fair.append([rec["mix"], d, t, tm["p99_us"],
                                      tm.get("slowdown_vs_solo", ""),
                                      m["fairness"]])
                print(f"[tail] {rec['mix']} {d}: fairness={m['fairness']:.3f}"
                      f" p99={m['p99_us']:.0f}us")
    _rows_to_csv(os.path.join(csv_dir, "tail_qd.csv"),
                 ["workload", "design", "qd", "p50_us", "p95_us", "p99_us",
                  "iops"], rows_qd)
    _rows_to_csv(os.path.join(csv_dir, "tail_fairness.csv"),
                 ["mix", "design", "tenant", "p99_us", "slowdown_vs_solo",
                  "fairness"], rows_fair)
    return records


def stream_replay(csv_dir, designs, smoke=False):
    """Chunked streaming-engine leg: a synthetic ~90 s trace — 4x beyond
    the int32 tick budget — replayed in 10 s windows through
    ``repro.ssd.stream``.  Exports per-window ``ios_per_wallclock_s`` (the
    flat-throughput acceptance surface: prep/compile overlap execution, so
    later windows must not droop) into BENCH_*.json and a CSV."""
    from repro.traces.generator import CUSTOM_TRACES, gen_trace, register_trace
    from repro.workloads.scenario import StreamReplay, run_scenario

    cfg = perf_optimized()
    n_req = 600 if smoke else 2000
    name = "stream90_synth"
    if name not in CUSTOM_TRACES:
        tr = dict(gen_trace("hm_0", n_req, seed=11))
        # respace arrivals uniformly over 90 s: same addresses and ordering,
        # beyond-budget timeline -> registered streaming-only.  Uniform load
        # per window makes per-window IO/s comparable, so the droop check
        # measures the engine, not the workload's burst profile.
        tr["arrival_us"] = np.arange(n_req, dtype=np.float64) * (90e6 / n_req)
        register_trace(name, tr)
    rec = run_scenario(cfg, StreamReplay(name, window_s=10.0), designs)
    tp = [w["ios_per_wallclock_s"] for w in rec["windows"] if w["n_requests"]]
    print(f"[stream] {rec['n_windows']} windows x {rec['window_s']:.0f}s, "
          f"{rec['n_requests']} reqs; IO/s first={tp[0]:.0f} "
          f"last={tp[-1]:.0f} flatness={rec['throughput_flatness']:.2f}")
    _rows_to_csv(os.path.join(csv_dir, "stream_windows.csv"),
                 ["window", "n_requests", "n_txns", "prep_s", "exec_s",
                  "compile_wait_s", "wall_s", "ios_per_wallclock_s"],
                 [[w["window"], w["n_requests"], w["n_txns"], w["prep_s"],
                   w["exec_s"], w["compile_wait_s"], w["wall_s"],
                   w["ios_per_wallclock_s"]] for w in rec["windows"]])
    return rec


def fault_degradation(csv_dir, designs, smoke=False):
    """Degraded-mode leg (ISSUE 8): the same workload replayed under
    growing per-channel link-fault counts; exports each design's
    throughput retention (``iops_ok`` vs its own fault-free run) and
    permanent-failure rate into ``fault_degradation.csv`` + the
    ``faults`` key of BENCH_*.json.  The acceptance asymmetry: Venice's
    adaptive DFS routes around dead links while a shared-bus design
    loses the whole channel."""
    from repro.workloads.scenario import DegradedModeSweep, run_scenario

    cfg = perf_optimized()
    counts = (0, 1, 2) if smoke else (0, 1, 2, 4, 8)
    rec = run_scenario(
        cfg,
        DegradedModeSweep("hm_0", fault_counts=counts,
                          placement="per_channel",
                          n_requests=(240 if smoke else 800)),
        designs,
    )
    rows = []
    for d, curve in rec["designs"].items():
        for k, m in curve.items():
            rows.append([rec["workload"], rec["placement"], d, k,
                         m["iops_ok"], m["retention"], m["failure_pct"]])
        worst = curve[str(counts[-1])]
        print(f"[faults] {d}: retention@{counts[-1]}"
              f"={worst['retention']:.3f} "
              f"failures={worst['failure_pct']:.1f}%")
    _rows_to_csv(os.path.join(csv_dir, "fault_degradation.csv"),
                 ["workload", "placement", "design", "failed_links",
                  "iops_ok", "retention", "failure_pct"], rows)
    return rec


def tab4_overheads(csv_dir):
    """Analytic reproduction of Table 4 / §6.6 arithmetic."""
    router_mw = 0.241
    link_mw = 1.08
    n_links = 112
    n_routers = 64
    router_area_mm2 = 8.0  # incl. I/O pads
    chip_area_mm2 = 100.0
    link_area_rel = 0.04  # x flash channel area
    pcb_router_pct = router_area_mm2 / chip_area_mm2 * 100
    link_area_total = 1 - (n_links * link_area_rel) / (8 * 1.0)
    print(f"[tab4] router power {router_mw}mW x{n_routers}, link {link_mw}mW")
    print(f"[tab4] router PCB overhead {pcb_router_pct:.0f}% of flash chip")
    print(f"[tab4] links occupy {link_area_total*100:.0f}% LESS area than "
          f"the 8 shared channels (paper: 44%)")
    _rows_to_csv(os.path.join(csv_dir, "tab4_overheads.csv"),
                 ["quantity", "value"],
                 [["router_power_mw", router_mw],
                  ["link_power_mw_4KB", link_mw],
                  ["router_pcb_overhead_pct", f"{pcb_router_pct:.1f}"],
                  ["link_area_saving_pct", f"{link_area_total*100:.1f}"]])
    assert abs(link_area_total - 0.44) < 0.01  # matches the paper's §6.6


def sec31_example(csv_dir=None):
    """The §3.1 two-read example; returns the two completion times in
    ticks (same channel, different channels).  ``csv_dir=None`` writes no
    CSV."""
    from repro.ssd import simulate

    cfg = perf_optimized(bus_protocol_ovh_ns=0.0, chan_gbps=1.024)

    def mk(planes):
        n = len(planes)
        planes = np.asarray(planes, np.int64)
        chips = planes // 2
        return {
            "arrival": np.zeros(n, np.int64), "kind": np.zeros(n, np.int64),
            "plane": planes, "node": chips, "row": chips // cfg.cols,
            "nbytes": np.full(n, 4096, np.int64),
            "req": np.arange(n, dtype=np.int64),
        }

    ticks = (simulate(cfg, mk([0, 2]), "baseline").exec_ticks,
             simulate(cfg, mk([0, 16]), "baseline").exec_ticks)
    conflict, free = (t / 100 for t in ticks)
    print(f"[sec3.1] same-channel two reads: {conflict:.2f}us (paper 11.01)")
    print(f"[sec3.1] diff-channel two reads: {free:.2f}us (paper 7.01)")
    if csv_dir is not None:
        _rows_to_csv(os.path.join(csv_dir, "sec31_example.csv"),
                     ["case", "us", "paper_us"],
                     [["same_channel", f"{conflict:.2f}", 11.01],
                      ["different_channels", f"{free:.2f}", 7.01]])
    return ticks


def _parse_designs(arg: str | None):
    if not arg:
        return DEFAULT_DESIGNS
    if arg == "all":
        return ALL_DESIGNS
    designs = tuple(d.strip() for d in arg.split(",") if d.strip())
    unknown = [d for d in designs if d not in ALL_DESIGNS]
    if unknown:
        raise SystemExit(f"unknown designs {unknown}; registry: {ALL_DESIGNS}")
    if "baseline" not in designs:  # speedups/energy are baseline-normalized
        print("[benchmarks] adding 'baseline' lane (normalization reference)")
        designs = ("baseline",) + designs
    return designs


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="all 19 workloads + 6 mixes (slow)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI probe: 1 workload x 2 designs, core phases only")
    ap.add_argument("--only", default=None,
                    help="fig4|fig9|fig11|fig12|fig14|fig15|tail|stream|"
                         "faults|tab4|sec31")
    ap.add_argument("--csv", default="results")
    ap.add_argument("--n-req", type=int, default=None)
    ap.add_argument("--designs", default=None, metavar="D1,D2,...",
                    help="design lanes to sweep (default: the paper's six; "
                         "'all' = every registered design incl. ablations)")
    ap.add_argument("--ftl-engine", default="auto",
                    choices=("auto", "vector", "scalar"),
                    help="trace-decomposition engine (scalar = the "
                         "page-at-a-time oracle, for FTL-pipeline A/Bs)")
    ap.add_argument("--lane-backend", default=None,
                    choices=("xla", "pallas", "pallas-interpret", "auto"),
                    help="lane-step kernel for batched static groups "
                         "(default: REPRO_LANE_BACKEND or xla) — lets a "
                         "--smoke leg A/B the Pallas kernel against the "
                         "one-hot XLA step without code edits; every "
                         "backend is bit-exact")
    ap.add_argument("--json", nargs="?", const="", default=None,
                    metavar="PATH",
                    help="write a BENCH_*.json perf-trajectory artifact "
                         "(ftl_s/sim_s per phase + per-design speedups)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome-trace-event JSON (Perfetto "
                         "loadable): per-transaction device timelines + "
                         "resource occupancy tracks AND harness "
                         "compile/dispatch/stream spans in one view; a "
                         "resource-utilization/conflict heatmap CSV lands "
                         "next to it.  Reconstructed from SimResult arrays "
                         "after the fact — figure CSVs stay byte-identical")
    args = ap.parse_args()
    if args.smoke and args.full:
        raise SystemExit("--smoke and --full are mutually exclusive")

    if args.trace_out:
        from repro import obs

        obs.enable_tracing()
    from repro.obs import spans as obs_spans

    bench.FTL_ENGINE = args.ftl_engine
    if args.lane_backend is not None:
        sim.LANE_BACKEND = args.lane_backend
    if args.smoke:
        designs = _parse_designs(args.designs or ",".join(SMOKE_DESIGNS))
        workloads = SMOKE_WL
        n_req = args.n_req or N_REQ_SMOKE
        mixes = ["mix1"]
    else:
        designs = _parse_designs(args.designs)
        workloads = sorted(WORKLOADS) if args.full else QUICK_WL
        n_req = args.n_req or (None if args.full else N_REQ_QUICK)
        mixes = None if args.full else ["mix1", "mix5"]
    t0 = time.time()
    phases: dict[str, dict] = {}
    speedups = {}

    def want(name):
        if args.only is not None:  # explicit --only wins, also under --smoke
            return args.only in ALIASES.get(name, (name,))
        return not args.smoke or name in SMOKE_PHASES

    ALIASES = {"fig4_9_10_13": ("fig4", "fig9", "fig10", "fig13")}

    # ---- cross-phase compile prefetch (overlapped pipeline, DESIGN §2.2):
    # the planner knows every phase's request shapes up front, so the whole
    # preset's missing executables start compiling NOW, on the background
    # compile pool, while the early phases execute.  A hint only — a stale
    # list just means the compile happens at first use.
    pre = []
    if want("fig4_9_10_13"):
        pre += [RunRequest(wl, cfg, designs, n_req)
                for cfg in (perf_optimized(), cost_optimized())
                for wl in workloads]
    if not args.smoke:
        if want("fig11"):
            pre += [RunRequest(wl, perf_optimized(), designs, n_req)
                    for wl in FIG11_WLS]
        if want("fig12"):
            pre += [RunRequest(mix, perf_optimized(), designs, n_req)
                    for mix in (mixes or sorted(MIXES))]
        if want("fig15"):
            d15 = tuple(d for d in designs if d != "pnssd")
            pre += [RunRequest(wl, perf_optimized(rows=r, cols=c), d15,
                               n_req)
                    for (r, c) in FIG15_MESHES for wl in FIG15_WLS]
    # the QoS phase's small-lane programs (quick/full tail only: the smoke
    # tail runs one lane per feedback round, below every layout window)
    extra = (prewarm_small_keys(perf_optimized(), 2048)
             if want("tail") and not args.smoke else [])
    if pre or extra:
        precompile(pre, extra_keys=extra)

    def phase(name, fn, *a, **kw):
        t = time.time()
        f0, s0 = bench.PERF["ftl_s"], bench.PERF["sim_s"]
        c0, e0 = bench.PERF["compile_s"], bench.PERF["exec_s"]
        l0, g0 = bench.PERF["lanes"], len(bench.PERF["groups"])
        w0, o0 = bench.PERF["compile_wait_s"], bench.PERF["compile_overlap_s"]
        bench.PERF["phase"] = name  # run-cache provenance (bench.WorkloadRun)
        try:
            with obs_spans.span("phase", name):
                out = fn(*a, **kw)
        finally:
            bench.PERF["phase"] = None
        cache = bench.PERF["phase_cache"].get(name, {})
        phases[name] = {
            "s": round(time.time() - t, 2),
            "ftl_s": round(bench.PERF["ftl_s"] - f0, 3),
            "sim_s": round(bench.PERF["sim_s"] - s0, 3),
            "compile_s": round(bench.PERF["compile_s"] - c0, 3),
            "exec_s": round(bench.PERF["exec_s"] - e0, 3),
            "compile_wait_s": round(bench.PERF["compile_wait_s"] - w0, 3),
            "compile_overlap_s": round(
                bench.PERF["compile_overlap_s"] - o0, 3),
            "lanes": bench.PERF["lanes"] - l0,
            "groups": len(bench.PERF["groups"]) - g0,
            # a fully-cached phase used to report s=0/lanes=0 as if it
            # hadn't run at all; these two fields distinguish "free" (runs
            # served from the cache, with the phase that paid for them)
            # from "not run"
            "cache_hits": cache.get("hits", 0),
            "cache_from": cache.get("from", {}),
        }
        return out

    if want("fig4_9_10_13"):
        speedups = phase("fig4_9_10_13", fig4_and_9_and_10_and_13,
                         workloads, n_req, args.csv, designs)
    if want("fig11"):
        phase("fig11", fig11_tail_latency, n_req, args.csv, designs)
    if want("fig12"):
        phase("fig12", fig12_mixes, n_req, args.csv, designs, mixes)
    if want("fig14"):
        phase("fig14", fig14_power_energy, workloads[:4], n_req, args.csv,
              designs)
    if want("fig15"):
        phase("fig15", fig15_sensitivity, n_req, args.csv, designs)
    tail_records = []
    if want("tail"):
        tail_records = phase("tail", tail_qos, n_req, args.csv, designs,
                             smoke=args.smoke)
    stream_record = None
    if want("stream"):
        stream_record = phase("stream", stream_replay, args.csv, designs,
                              smoke=args.smoke)
    fault_record = None
    if want("faults"):
        fault_record = phase("faults", fault_degradation, args.csv, designs,
                             smoke=args.smoke)
    if want("tab4"):
        phase("tab4", tab4_overheads, args.csv)
    if want("sec31"):
        phase("sec31", sec31_example, args.csv)
    total = round(time.time() - t0, 2)
    ftl_total = round(bench.PERF["ftl_s"], 3)
    sim_total = round(bench.PERF["sim_s"], 3)
    print(f"[benchmarks] total {total}s (ftl {ftl_total}s, sim {sim_total}s, "
          f"engine={args.ftl_engine}); CSVs in {args.csv}/")

    if args.json is not None:
        path = args.json or os.path.join(
            args.csv, f"BENCH_{time.strftime('%Y%m%d_%H%M%S')}.json"
        )
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        artifact = {
            "preset": ("smoke" if args.smoke
                       else "full" if args.full else "quick"),
            "generated_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
            "only": args.only,
            "n_req": n_req,
            "designs": list(designs),
            "workloads": workloads,
            "ftl_engine": args.ftl_engine,
            "phases": phases,
            "ftl_s_total": ftl_total,
            "sim_s_total": sim_total,
            "cache": {k: bench.PERF[k] for k in
                      ("decomp_hits", "decomp_misses", "run_hits",
                       "run_subset_hits", "run_misses", "run_prefetched")},
            # the overlapped compile/execute pipeline split
            "compile_overlap_s": round(
                bench.PERF["compile_overlap_s"], 3),
            "compile_wait_s": round(bench.PERF["compile_wait_s"], 3),
            # sweep-planner attribution: lane/step counts, devices, and the
            # per-group compile-vs-execute split (satellite: make the
            # speedup attributable)
            "lanes": bench.PERF["lanes"],
            "scan_steps": {
                "valid": bench.PERF["scan_steps_valid"],
                "padded": bench.PERF["scan_steps_padded"],
            },
            "devices_used": bench.PERF["devices_used"],
            "compile_s_total": round(bench.PERF["compile_s"], 3),
            "exec_s_total": round(bench.PERF["exec_s"], 3),
            "groups": bench.PERF["groups"],
            # kernel-dispatch split: which lane-step kernel each group ran
            # (xla / pallas-interpret / pallas-compiled) and the share of
            # lane-steps served by the batched runners — static and scout
            # lanes tallied separately (the scout split is ISSUE 10's
            # figure of merit)
            "kernel_dispatch": {
                "lane_backend": sim.resolve_lane_backend(),
                "planner_profile": sweep_plan.planner_profile(),
                "backends": bench.PERF["kernel_backends"],
                "steps_batched": bench.PERF["steps_batched"],
                "steps_unbatched": bench.PERF["steps_unbatched"],
                "batched_share": round(
                    bench.PERF["steps_batched"]
                    / max(bench.PERF["steps_batched"]
                          + bench.PERF["steps_unbatched"], 1), 4),
                "steps_scout_batched": bench.PERF["steps_scout_batched"],
                "steps_scout_unbatched":
                    bench.PERF["steps_scout_unbatched"],
                "scout_batched_share": round(
                    bench.PERF["steps_scout_batched"]
                    / max(bench.PERF["steps_scout_batched"]
                          + bench.PERF["steps_scout_unbatched"], 1), 4),
            },
            # accelerated-replay audit: per-(workload, config) scale factor
            # and offered utilization (satellite — previously dropped)
            "accel": bench.PERF["accel"],
            # QoS surface: per-design p50/p95/p99 + per-tenant fairness
            # from the tail phase's scenarios
            "tail": tail_records,
            # degraded-mode fault sweep: per-design throughput retention
            # under growing per-channel link faults
            "faults": fault_record,
            # streaming engine: per-window throughput of the beyond-budget
            # replay (acceptance: flat, compile_wait ~0 after window 1)
            "stream": stream_record,
            "stream_windows": bench.PERF["stream_windows"],
            "stream_prep_s": round(bench.PERF["stream_prep_s"], 3),
            "total_s": total,
            "speedups_geomean": {
                cfg: {d: round(v, 4) for d, v in per.items()}
                for cfg, per in speedups.items()
            },
        }
        with open(path, "w") as f:
            json.dump(artifact, f, indent=2)
        print(f"[benchmarks] perf trajectory written to {path}")

    if args.trace_out:
        from repro import obs

        heat = os.path.splitext(args.trace_out)[0] + ".heatmap.csv"
        info = obs.export_trace(args.trace_out, heatmap_csv=heat)
        print(f"[benchmarks] trace written to {args.trace_out} "
              f"({info['n_events']} events, {info['n_txn']} transactions); "
              f"heatmap in {heat}")


if __name__ == "__main__":
    main()
