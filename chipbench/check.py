"""The comparison that decides ``correct``.

After the window, a sample of its design runs, drawn from the run's seed,
is recomputed by the plain reference (``chipbench/reference``) from the
same byte trace: the benchmark's own replay scaling, page split and FTL,
then the reference lane.  Per design run it compares, element by element,
what the timed path produced:

* per transaction, in scan order: completion, wait, conflict, hops,
  tries, misroutes, failed (a transaction the two disagree on, or one only
  one of them has, counts once);
* per host request: latency, and the run's simulated execution time.

The simulator is exact (integer ticks), so each count's limit is 0.  Which
design runs are sampled is the traffic mix's ``check`` list: each entry
draws ``count`` runs among its ``designs`` (and ``workloads``, if given),
so every cell checks each cost class its ``why`` names.

The control is the same reference at a coarser clock (``tick_ns`` doubled,
times scaled back), the precision a faster simulator would be tempted to
drop to; ``chipbench/control.py`` shows it fails.
"""
from __future__ import annotations

import time

import numpy as np

from chipbench import tracegen
from chipbench.reference import ftl as ref_ftl
from chipbench.reference import lane as ref_lane
from chipbench.reference.ssdconfig import SSD

TXN_FIELDS = ("completion", "wait", "conflict", "hops", "tries", "misroutes",
              "failed")
TIME_FIELDS = ("completion", "wait")
# the simulator seeds every lane of request ``r`` with ``r.seed + 7``
LANE_SEED_OFFSET = 7


def sample(cell: dict, seed: int, pool: list, n_done: int) -> list:
    """``(sweep, workload, trace_seed, lane_seed, design)`` of the design
    runs to check, drawn from ``seed`` among the ``n_done`` sweeps run."""
    rng = np.random.default_rng([seed, 1])
    picks = []
    for group in cell["traffic"]["check"]:
        wls = group.get("workloads")
        cands = [(k, w, ts, ls, d) for k in range(n_done)
                 for w, ts, ls in pool[k] if wls is None or w in wls
                 for d in group["designs"]]
        idx = rng.choice(len(cands), size=min(group["count"], len(cands)),
                         replace=False)
        picks.extend(cands[i] for i in sorted(idx))
    return picks


def reference_txns(ssd: SSD, conf: dict, trace: dict) -> dict:
    acc = tracegen.accelerate(trace, ssd.chan_gbps, ssd.rows,
                              conf["target_util"])
    return ref_ftl.decompose(ssd, tracegen.to_pages(acc, ssd.page_bytes))


def compare(prog, ref: dict, scale: int = 1) -> dict:
    """Mismatch counts of one design run: ``prog`` a simulator SimResult,
    ``ref`` the reference's outputs, its times in ticks ``scale`` times
    the simulator's."""
    n_p, n_r = len(prog.completion), len(ref["completion"])
    n = min(n_p, n_r)
    bad = np.zeros((n,), dtype=bool)
    for f in TXN_FIELDS:
        r = np.asarray(ref[f][:n], dtype=np.int64)
        if f in TIME_FIELDS:
            r = r * scale
        bad |= np.asarray(getattr(prog, f), dtype=np.int64)[:n] != r
    lat_p = np.asarray(prog.req_latency, dtype=np.int64)
    lat_r = np.asarray(ref["req_latency"], dtype=np.int64) * scale
    m = min(len(lat_p), len(lat_r))
    return dict(
        txn_mismatch=int(bad.sum()) + abs(n_p - n_r),
        req_mismatch=(int((lat_p[:m] != lat_r[:m]).sum())
                      + abs(len(lat_p) - len(lat_r))
                      + int(prog.exec_ticks != ref["exec_ticks"] * scale)),
    )


def check_sample(cell: dict, seed: int, sweeper, results: list, pool: list,
                 *, control: bool = False, log=print) -> dict:
    """Recompute the sample with the reference (or with the control when
    ``control``) and compare.  Returns ``correct``, ``failed`` (design runs
    that differ) and ``checks`` (each number with its limit)."""
    conf = cell["config"]
    tick = conf["tick_ns"] * (2 if control else 1)
    ssd = SSD.from_file(conf, tick_ns=tick)
    scale = tick // conf["tick_ns"]
    picks = sample(cell, seed, pool, len(results))
    t0 = time.perf_counter()
    txns_of = {}
    totals = dict(txn_mismatch=0, req_mismatch=0)
    failed = 0
    for k, w, ts, ls, d in picks:
        if (w, ts) not in txns_of:
            trace = sweeper.traces[w, ts, sweeper.n_req]
            txns_of[w, ts] = reference_txns(ssd, conf, trace)
        ref = ref_lane.simulate(ssd, txns_of[w, ts], d,
                                ls + LANE_SEED_OFFSET)
        c = compare(results[k][w, ts, d], ref, scale)
        failed += int(any(c.values()))
        for key, v in c.items():
            totals[key] += v
        log(f"[check] {w} trace {ts} {d}: {len(ref['completion'])} "
            f"transactions, {c}")
    log(f"[check] {len(picks)} design runs in "
        f"{time.perf_counter() - t0:.1f} s"
        f"{' (control: tick ' + str(tick) + ' ns)' if control else ''}")
    checks = {k: dict(value=v, limit=0) for k, v in totals.items()}
    correct = bool(picks) and not any(totals.values())
    return dict(correct=correct, failed=failed, checks=checks)
