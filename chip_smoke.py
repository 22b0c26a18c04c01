"""Smoke run of the SSD design-sweep simulator on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sweep slice, sharded over 4 chips

Drives the main path once, through the entry points a user calls, at the
paper's 8x8 Table-1 geometry, and checks what comes out:

1. the §3.1 two-read example through ``simulate``: 11.01 us and 7.01 us,
   exactly;
2. a fig4/9/10/13 slice — workloads hm_0 and proj_3 at the quick preset's
   2,500 requests, the perf- and cost-optimized configs, the paper's six
   designs — planned by ``sweep_plan.prefetch`` and read back through
   ``bench.run_workload``.  On a TPU the planner must take its
   ``occupancy`` profile with the compiled Pallas kernels: static lanes
   in the batched kernel, venice lanes in the batched scout kernel, and
   no child process;
3. hm_0 on the perf config, baseline and venice lanes, element by element
   against the plain-Python reference (``scalar_ref.simulate_ref``):
   ``completion``, ``wait`` and ``failed`` over the whole trace.

``--chips 4`` runs only phases 2 and 3 with the lane groups sharded over
four chips; its completion digest must equal the one-chip run's.

Without a TPU it exits non-zero and prints no result.  Seconds printed
are one unmeasured run (compile counted as set-up), not a benchmark.  The
last line of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
WORKLOADS = ("hm_0", "proj_3")
REF_WORKLOAD, REF_DESIGNS = "hm_0", ("baseline", "venice")
REF_FIELDS = ("completion", "wait", "failed")


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: check failed: {what}")


def sec31() -> None:
    from benchmarks.run import sec31_example

    ticks = sec31_example()
    check(ticks == (1101, 701), f"§3.1 ticks {ticks} != (1101, 701)")


def sweep_slice(n_chips: int) -> list:
    """Phase 2: the fig4/9/10/13 slice through the planner; returns the
    WorkloadRuns in (config, workload) order."""
    from benchmarks.run import DEFAULT_DESIGNS, N_REQ_QUICK
    from repro.ssd import bench, cost_optimized, perf_optimized, sim
    from repro.ssd import sweep_plan
    from repro.ssd.bench import geomean
    from repro.ssd.sweep_plan import RunRequest, prefetch

    check(sweep_plan.planner_profile() == "occupancy",
          f"planner profile {sweep_plan.planner_profile()}")
    check(sim.resolve_lane_backend() == "pallas",
          f"lane backend {sim.resolve_lane_backend()}")
    cfgs = (perf_optimized(), cost_optimized())
    prefetch([RunRequest(wl, cfg, DEFAULT_DESIGNS, N_REQ_QUICK)
              for cfg in cfgs for wl in WORKLOADS])
    runs = [bench.run_workload(wl, cfg, designs=DEFAULT_DESIGNS,
                               n_requests=N_REQ_QUICK)
            for cfg in cfgs for wl in WORKLOADS]

    perf = bench.PERF
    backends = dict(perf["kernel_backends"])
    batched = [g for g in perf["groups"]
               if g["variant"] in ("batched", "bscout")]
    print(f"[slice] compile_s {perf['compile_s']:.3f} (set-up), exec_s "
          f"{perf['exec_s']:.3f} (one unmeasured run, device_get after "
          f"each group)")
    print(f"[slice] lanes {perf['lanes']}, lane-steps valid "
          f"{perf['scan_steps_valid']} padded {perf['scan_steps_padded']}, "
          f"groups {len(perf['groups'])}, devices_used "
          f"{perf['devices_used']}")
    print(f"[slice] kernel backends {backends}, steps_batched "
          f"{perf['steps_batched']}, steps_scout_batched "
          f"{perf['steps_scout_batched']}")
    check(batched and all(g["kernel_backend"] == "pallas-compiled"
                          for g in batched),
          f"batched groups not all pallas-compiled: {backends}")
    check("pallas-interpret" not in backends, f"interpreted: {backends}")
    check(perf["steps_batched"] > 0, "no static lane-steps batched")
    check(perf["steps_scout_batched"] > 0, "no scout lane-steps batched")
    check(perf["devices_used"] == n_chips,
          f"devices_used {perf['devices_used']} != {n_chips}")
    for cfg in cfgs:
        mine = [r for r in runs if r.cfg == cfg]
        print(f"[slice/{cfg.name}] geomean speedups: " + " ".join(
            f"{d}={geomean(r.speedup(d) for r in mine):.4f}x"
            for d in DEFAULT_DESIGNS))
    h = hashlib.sha256()
    for r in runs:
        for d in DEFAULT_DESIGNS:
            h.update(r.results[d].completion.astype("<i4").tobytes())
    print(f"[slice] completion sha256 {h.hexdigest()}")
    return runs


def reference(runs: list) -> None:
    """Phase 3: the reference lanes, element by element."""
    import numpy as np
    from repro.ssd import bench, perf_optimized
    from repro.ssd.scalar_ref import simulate_ref
    from repro.traces.generator import to_pages, trace_for
    from benchmarks.run import N_REQ_QUICK

    cfg = perf_optimized()
    run = next(r for r in runs if r.name == REF_WORKLOAD and r.cfg == cfg)
    # the planner's own inputs: accelerated replay, cached decomposition,
    # lane seed 7 (``sweep_plan._sims_for``)
    trace, _ = bench.accelerate(trace_for(REF_WORKLOAD, N_REQ_QUICK, 0),
                                cfg, 1.5)
    pages = to_pages(trace, cfg.page_bytes)
    txns = bench.decompose_cached(cfg, pages, int(pages["footprint_pages"]))
    for d in REF_DESIGNS:
        ref = simulate_ref(cfg, txns, d, seed=7)
        res = run.results[d]
        for f in REF_FIELDS:
            check(np.array_equal(np.asarray(getattr(res, f)), ref[f]),
                  f"{REF_WORKLOAD}/{d}: {f} differs from scalar_ref")
        print(f"[ref] {REF_WORKLOAD}/{cfg.name}/{d}: {len(ref['completion'])}"
              f" transactions equal to scalar_ref ({', '.join(REF_FIELDS)})")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sweep slice, sharded over 4 chips")
    args = ap.parse_args()
    if args.chips == 1:
        # one chip however many the host has (set before the TPU runtime
        # starts; a caller's own choice wins)
        for var, val in (("TPU_VISIBLE_CHIPS", "0"),
                         ("TPU_CHIPS_PER_PROCESS_BOUNDS", "1,1,1"),
                         ("TPU_PROCESS_BOUNDS", "1,1,1")):
            os.environ.setdefault(var, val)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from repro.xla_env import configure

    configure()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devs[0].platform}); this "
              "smoke runs on the chip only", file=sys.stderr)
        return 2
    check(len(devs) == args.chips,
          f"{len(devs)} devices visible, --chips {args.chips}")
    print(f"[device] {devs[0].device_kind} x{len(devs)}")
    t0 = time.perf_counter()
    runs = sweep_slice(args.chips)
    reference(runs)
    if args.chips == 1:
        sec31()
    print(f"[total] {time.perf_counter() - t0:.1f}s wall (one unmeasured "
          "run, compiles included)")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
