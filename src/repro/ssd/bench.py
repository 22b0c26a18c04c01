"""Benchmark harness: trace → FTL → per-design simulation → paper metrics.

Methodology note (documented in DESIGN.md / EXPERIMENTS.md): the paper replays
week-long enterprise traces whose *bursts* saturate the device even though the
Table-2 mean inter-arrival times look sparse.  Our synthetic traces match the
Table-2 statistics exactly; to reproduce the paper's saturation regime we use
*accelerated replay* (standard MQSim-style methodology): arrivals are scaled
so the offered load reaches ``target_util`` of the baseline's aggregate
channel bandwidth (never decelerated).  Table-2 statistics are validated on
the unscaled traces in the test suite; fig-13 conflict rates and fig-9/10
speedup magnitudes are validated on the accelerated replays.
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import time
from typing import Dict, Iterable

import numpy as np

from repro.obs import spans as obs_spans
from repro.obs.registry import MetricsRegistry, PerfDict
from repro.ssd.config import SSDConfig
from repro.ssd.ftl import Transactions, decompose_trace
from repro.ssd.sim import SimResult


@dataclasses.dataclass
class WorkloadRun:
    name: str
    cfg: SSDConfig
    accel: float
    n_requests: int
    results: Dict[str, SimResult]
    # figure phase that actually paid for this run (None outside the
    # benchmark harness): lets a later phase served from the run cache
    # report WHERE its "free" results came from instead of lying s=0/lanes=0
    origin_phase: str | None = None

    def speedup(self, design: str, base: str = "baseline") -> float:
        return self.results[base].exec_s / self.results[design].exec_s

    def iops_norm(self, design: str, base: str = "ideal") -> float:
        return self.results[design].iops() / self.results[base].iops()


def offered_utilization(trace, cfg: SSDConfig) -> float:
    """Offered load as a fraction of aggregate shared-channel bandwidth."""
    span_us = float(trace["arrival_us"][-1] - trace["arrival_us"][0])
    tot_bytes = float(np.sum(trace["size_bytes"]))
    bw_bytes_per_us = cfg.chan_gbps * 1e3 * cfg.rows  # GB/s == KB/ms == B/us*1e3
    return tot_bytes / max(span_us, 1e-9) / bw_bytes_per_us


def accelerate(trace, cfg: SSDConfig, target_util: float = 1.5) -> tuple:
    """Scale arrivals to reach ``target_util`` offered load (never slow down)."""
    u = offered_utilization(trace, cfg)
    factor = max(1.0, target_util / max(u, 1e-9))
    if factor > 1.0:
        trace = dict(trace)
        trace["arrival_us"] = trace["arrival_us"] / factor
    return trace, factor


def record_accel(name: str, cfg: SSDConfig, factor: float, offered: float,
                 target_util: float | None) -> None:
    """Audit one (possibly) accelerated replay in ``PERF["accel"]`` —
    the scale factor and the offered utilization before/after scaling
    (exported verbatim into BENCH_*.json's ``accel`` key)."""
    PERF["accel"][f"{name}/{cfg.name}"] = {
        "factor": round(factor, 4),
        "offered_util": round(offered, 5),
        "offered_util_replayed": round(offered * factor, 5),
        "target_util": target_util,
    }


# Per-process perf accounting: wall-clock split between the FTL front end
# (trace → transactions) and the jitted sweep, plus cache telemetry and the
# sweep planner's execution counters — lanes dispatched, trimmed-vs-valid
# scan steps, host devices used, and the per-group compile-vs-execute split
# (``groups`` holds one record per dispatched lane group) so every speedup
# in a BENCH_*.json is attributable.  ``benchmarks/run.py`` snapshots these
# around each figure phase.
#
# Declared through the structured metrics registry (ISSUE 9): ``PERF`` is
# a :class:`repro.obs.registry.PerfDict` — still a real dict with exactly
# the historical keys (BENCH_*.json schema unchanged, every ``perf["x"] +=``
# call site untouched) — gaining typed declarations plus
# ``reset()``/``snapshot()``/``delta()`` semantics so scenario engines can
# report per-run counter deltas instead of process-cumulative ones.
METRICS = MetricsRegistry()
METRICS.timer("ftl_s")
METRICS.timer("sim_s")
for _c in ("decomp_hits", "decomp_misses", "run_hits", "run_subset_hits",
           "run_misses", "run_prefetched", "lanes", "scan_steps_valid",
           "scan_steps_padded"):
    METRICS.counter(_c)
# batched scout groups: the DFS steps of each lane's own walks (every try
# and raced scout of its valid transactions), and the DFS while-loop's
# iterations times the lanes stepped in lockstep with them; the retry
# loop's rounds (walk calls) and the scan steps, each per shard, and the
# tries walked, speculative ones included, of the lanes that are not padding
for _c in ("dfs_steps_live", "dfs_steps_padded", "scout_walk_calls",
           "scout_scan_steps", "scout_tries_walked"):
    METRICS.counter(_c)
METRICS.gauge("devices_used", 0)
METRICS.timer("compile_s")
METRICS.timer("exec_s")
METRICS.object("groups", [])
# host stages of a planned batch around the chip's work, each also a
# program span (``stage``; ``sim._run_compiled``): lowering runs to lanes
# and planning groups, packing a group's arguments, enqueueing their copy
# to the device (``put_bytes``: the bytes placed; the copy itself runs
# inside ``exec_s``), reading the outputs back once the chip is done
# (inside ``exec_s``), unpacking lane outputs into results
for _t in ("lower_s", "pack_s", "put_s", "fetch_s", "unpack_s"):
    METRICS.timer(_t)
METRICS.counter("put_bytes")
# the overlapped compile/execute pipeline split (DESIGN.md §2.2):
# background compile time hidden behind execution vs time the dispatcher
# actually stalled
METRICS.timer("compile_overlap_s")
METRICS.timer("compile_wait_s")
# streaming engine (repro.ssd.stream): windows replayed and wall-clock
# spent in the overlapped prep stage (decompose + order + pack) — prep
# that hides behind execution shows up here but not in compile_wait_s
METRICS.counter("stream_windows")
METRICS.timer("stream_prep_s")
# kernel-dispatch split (ISSUE 7): per-backend group counts
# ({"xla"|"pallas-interpret"|"pallas-compiled": n}) and how many
# lane-steps ran through the batched static step vs the unbatched
# scan — the backend/batching share surfaced in BENCH_*.json's
# ``kernel_dispatch`` block and the trajectory table.  Scout lanes
# tally separately (ISSUE 10): their batched runner landed three PRs
# after the static one, so the scout split is the figure of merit.
METRICS.object("kernel_backends", {})
METRICS.counter("steps_batched")
METRICS.counter("steps_unbatched")
METRICS.counter("steps_scout_batched")
METRICS.counter("steps_scout_unbatched")
# current figure phase (set by benchmarks/run.py) + per-phase run-cache
# attribution: {phase: {"hits": n, "from": {origin_phase: n}}}
METRICS.gauge("phase", None)
METRICS.object("phase_cache", {})
# per-(workload, config) accelerated-replay audit trail: the
# ``accelerate()`` scale factor and the offered utilization before/after
# scaling (satellite: the factor used to be computed and dropped by
# ``run_workload`` callers, leaving replays unauditable).
METRICS.object("accel", {})

PERF: PerfDict = METRICS.view()


@contextlib.contextmanager
def stage(timer: str, name: str):
    """Time a host stage of the planner into ``PERF[timer]`` and record
    it as the program span ``name`` (main thread only: no lock)."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        t1 = time.perf_counter()
        PERF[timer] += t1 - t0
        obs_spans.interval("plan", name, t0, t1)

# The FTL engine the harness decomposes with ("auto" | "vector" | "scalar");
# benchmarks/run.py --ftl-engine flips this for A/B perf runs.
FTL_ENGINE = "auto"

# Completed runs, keyed by every input that affects the result.  Benchmark
# presets revisit the same (workload, config) pair across figure phases
# (fig9's runs serve fig10/13/14 and part of fig11); the sweep is
# deterministic, so memoizing whole WorkloadRuns removes that duplicate
# simulation work.  A true LRU: hits refresh recency (move-to-end — plain
# dicts preserve insertion order), eviction drops the least-recently-used
# entry, and subset hits are served as derived views WITHOUT inserting a
# duplicate entry (a derived copy of data the superset entry already holds
# would push out an unrelated run).
_RUN_CACHE: dict = {}
_RUN_CACHE_MAX = 24

# Decompositions, keyed on (trace content, FTL-relevant geometry): the FTL
# never sees interconnect or timing parameters, so every design lane, every
# figure phase and any config sharing (page size, array geometry, striping
# chunk) reuses one decomposition even when the WorkloadRun cache misses
# (different design sets, evictions).
_DECOMP_CACHE: dict = {}
_DECOMP_CACHE_MAX = 32


def _lru_get(cache: dict, key):
    hit = cache.pop(key, None)
    if hit is not None:
        cache[key] = hit  # re-insert: most-recently-used position
    return hit


def _lru_put(cache: dict, key, val, cap: int) -> None:
    cache.pop(key, None)
    while len(cache) >= cap:
        cache.pop(next(iter(cache)))  # least-recently-used
    cache[key] = val


def clear_caches() -> None:
    """Drop memoized runs/decompositions (tests, memory pressure)."""
    _RUN_CACHE.clear()
    _DECOMP_CACHE.clear()


def ftl_geometry(cfg: SSDConfig) -> tuple:
    """The SSDConfig fields the FTL decomposition depends on — nothing
    else (latencies, interconnect, power) can change the transaction
    stream, so configs differing only there share cache entries."""
    return (cfg.rows, cfg.cols, cfg.dies_per_chip, cfg.planes_per_die,
            cfg.pages_per_block, cfg.page_bytes, cfg.chunk_pages)


def _trace_digest(pages: Dict[str, np.ndarray]) -> bytes:
    h = hashlib.sha1()
    for k in ("arrival_us", "is_read", "offset_page", "n_pages"):
        h.update(np.ascontiguousarray(pages[k]).tobytes())
    if "tenant" in pages:  # attribution rides on the cached Transactions:
        # same arrays + different tags must not share an entry (the tagged
        # and untagged decompositions are bit-identical otherwise)
        h.update(b"tenant")
        h.update(np.ascontiguousarray(pages["tenant"]).tobytes())
    return h.digest()


def decompose_cached(
    cfg: SSDConfig,
    pages: Dict[str, np.ndarray],
    footprint_pages: int,
    overprovision: float = 1.28,
) -> Transactions:
    """``decompose_trace`` behind the content-keyed LRU (read-only result)."""
    key = (_trace_digest(pages), ftl_geometry(cfg), footprint_pages,
           overprovision, FTL_ENGINE)
    hit = _lru_get(_DECOMP_CACHE, key)
    if hit is not None:
        PERF["decomp_hits"] += 1
        return hit
    PERF["decomp_misses"] += 1
    txns = decompose_trace(cfg, pages, footprint_pages=footprint_pages,
                           overprovision=overprovision, engine=FTL_ENGINE)
    _lru_put(_DECOMP_CACHE, key, txns, _DECOMP_CACHE_MAX)
    return txns


def _cached_run(name, cfg, designs, n_requests, target_util, seed,
                count: bool = True) -> WorkloadRun | None:
    """Serve a run from the LRU (exact hit or superset-derived view).

    Sweep lanes are independent (the parity tests assert a lane is
    bit-identical to its standalone simulation), so a cached run over a
    SUPERSET of designs serves any subset — e.g. fig15's 8x8 leg reuses
    fig9's runs even though it drops pnssd.  Served as a derived view
    (refreshing the superset's recency), never cached under its own key.

    ``count=False`` makes this a silent probe (the planner's prefetch
    peeks without distorting the hit/miss telemetry — only the phase
    body's real ``run_workload`` calls are counted).
    """
    key = (name, cfg, designs, n_requests, target_util, seed)
    hit = _lru_get(_RUN_CACHE, key)
    if hit is not None:
        if count:
            PERF["run_hits"] += 1
            _count_phase_hit(hit)
        return hit
    for sup_key, run in list(_RUN_CACHE.items()):
        (n2, c2, d2, r2, u2, s2) = sup_key
        if ((n2, c2, r2, u2, s2) == (name, cfg, n_requests, target_util, seed)
                and set(designs) <= set(d2)):
            _lru_get(_RUN_CACHE, sup_key)
            if count:
                PERF["run_subset_hits"] += 1
                _count_phase_hit(run)
            return WorkloadRun(
                name=run.name, cfg=run.cfg, accel=run.accel,
                n_requests=run.n_requests,
                results={d: run.results[d] for d in designs},
                origin_phase=run.origin_phase,
            )
    return None


def _count_phase_hit(run: WorkloadRun) -> None:
    """Attribute one run-cache hit to the current figure phase, keyed by
    the phase that originally paid for the run — so a fully-cached phase's
    artifact says "served from fig9" instead of pretending it ran nothing."""
    phase = PERF.get("phase")
    if phase is None:
        return
    rec = PERF["phase_cache"].setdefault(phase, {"hits": 0, "from": {}})
    rec["hits"] += 1
    origin = run.origin_phase or "?"
    rec["from"][origin] = rec["from"].get(origin, 0) + 1


def run_workload(
    name: str,
    cfg: SSDConfig,
    designs: Iterable[str] = ("baseline", "pssd", "pnssd", "nossd", "venice", "ideal"),
    n_requests: int | None = None,
    target_util: float | None = 1.5,
    seed: int = 0,
) -> WorkloadRun:
    designs = tuple(designs)
    hit = _cached_run(name, cfg, designs, n_requests, target_util, seed)
    if hit is not None:
        return hit
    PERF["run_misses"] += 1
    # every miss routes through the sweep planner (one-request plan); figure
    # phases batch their whole workload list via ``sweep_plan.prefetch`` so
    # the lanes of many workloads/configs pool into shared sharded groups
    from repro.ssd.sweep_plan import RunRequest, execute_requests

    return execute_requests([
        RunRequest(name, cfg, designs, n_requests, target_util, seed)
    ])[0]


def geomean(xs) -> float:
    xs = np.asarray(list(xs), dtype=np.float64)
    return float(np.exp(np.mean(np.log(np.maximum(xs, 1e-12)))))
