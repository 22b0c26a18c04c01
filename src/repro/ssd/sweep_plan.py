"""Deferred sweep planner: conflict-free execution of the simulator itself.

The paper's thesis — exploit the parallelism the structure already gives you
by removing path conflicts — applied to the simulator: sweep lanes across
workloads, configs and seeds are fully independent, and within a
statically-routed lane the bus-design resources are disjoint per channel
row.  The planner turns both into wall-clock parallelism while keeping every
result bit-identical to the flat single-lane scan:

Channel decomposition (tentpole 1)
    A statically-routed lane whose lowered masks are *provably row-confined*
    (``designs.rows_confined`` — verified at lowering time, never assumed
    per design name) is split into one lane per channel row, scanning only
    that row's transactions.  Rows touch disjoint resources and disjoint
    planes, so per-resource commit order — and therefore every output — is
    unchanged; sequential scan length drops from N to ~max-row (~N/rows).
    Lanes that fail the proof (pnssd couples rows through its column buses,
    nossd selects FCs dynamically, scouts walk the global mesh) fall back
    to the flat scan.

Planning + multi-core sharding (tentpole 2)
    ``execute_sim_runs`` collects every pending (cfg, txns, designs, seeds)
    run, lowers each to lanes, and pools lanes by (geometry, cost class) —
    perf/cost configs of one geometry share a pool, and the two cost
    classes stay apart because lanes sharing a group's barrier must not
    pay each other's program cost (promotions and the scout ``k_max`` are
    pool-wide).  Pool lanes are sorted by chunk count and cut into
    ``shard_map`` groups of one lane per host CPU device
    (``--xla_force_host_platform_device_count``, set by
    ``benchmarks/run.py`` before jax initializes): the shards of a group
    execute in parallel inside one SPMD program while each lane stays
    UNBATCHED in its shard (vmap-batching lanes measured ~50x slower per
    scout step on CPU — see ``sim._build_group_fn``), and the sorting
    keeps a group's barrier cheap.  Every group of a pool shares one
    executable (tables/seed/txns/chunk-count are arguments).

Trimmed scans
    After grouping, each lane's scan runs only ``ceil(n / CHUNK)`` chunks
    of its capacity bucket (dynamic trip count, ``sim.CHUNK`` = 1024): the
    up-to-4x cond-skipped steps the power-of-4 buckets used to charge are
    gone, and padded-vs-valid step counts are recorded in ``bench.PERF``.

``bench.run_workload`` routes every cache miss through this planner;
``prefetch`` lets a figure phase hand over its whole workload list so one
planning pass serves the phase from the run cache.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import itertools
import os
import threading
import time
from typing import Sequence

import numpy as np

from repro.obs import events as obs_events
from repro.obs import spans as obs_spans
from repro.ssd import bench
from repro.ssd import exec_cache
from repro.ssd import sim as S
from repro.ssd.config import SSDConfig
from repro.ssd.designs import (
    KIND_SCOUT,
    LaneTables,
    lower_designs,
    mask_words_per_row,
    pregather_node_tables,
    pregather_scout_tables,
    resolve_specs,
    rows_confined,
)

__all__ = ["RunRequest", "execute_requests", "execute_sim_runs", "prefetch",
           "precompile", "prewarm_small_keys"]

# "auto" channel-decomposes a row-confined lane only when every row is
# expected to span several chunks (n >= rows * this * CHUNK): each row-lane
# pays chunk round-up, so short traces cost more as rows than they save in
# scan depth.  Policy only: decomposed and flat scans are bit-identical.
AUTO_DECOMPOSE_MIN_CHUNKS_PER_ROW = 4

# Capacity high-water mark per geometry signature: a pool reuses the
# largest capacity bucket its geometry has seen so executables keyed on
# capacity are not recompiled for smaller later pools (execute time scales
# with the trimmed chunk count, not the capacity).
_CAP_SEEN: dict = {}

# ---- small-lane policy (perf only; every layout is bit-identical) --------
# A lane at or below this many scan chunks counts as "small": small-lane
# pools are dispatch-bound (the QoS tail phase: hundreds of 1-2 chunk
# scans), so the planner collapses them — the measured policy, see
# DESIGN.md §2.2 and the A/B table in EXPERIMENTS.md:
#
#   * a small STATIC set of <= n_shards * _BATCH_MAX_PER_SHARD lanes runs
#     in the gather-free batched runner as ONE dispatch.  The per-shard
#     width cap is the measured fork/join cliff of XLA:CPU's parallel
#     task assigner: at ~[8, R_pad] int32 per op it starts splitting
#     every elementwise op across the intra-op pool, and the per-op
#     fork/join tax (~50-80us/step) dwarfs the batching win.  Below the
#     cliff the batched step runs ~0.5us per lane-step vs ~2.4us
#     unbatched — the PR-3 "50x slower" verdict was a property of the
#     vmap gather/scatter lowering, not of batching;
#   * any larger small-lane set — static or scout — runs as STACK groups:
#     K sequential unbatched lanes per shard (lax.map), one dispatch per
#     n_shards*K lanes, immune to the fork/join cliff.
#
# Above SMALL_LANE_MAX_CHUNKS chunks the flat sharded scan wins (the
# dispatch barrier amortizes, and a 3+-chunk lane is usually served by an
# already-compiled flat executable — pulling it into a small-lane layout
# would BUY a compile to save a dispatch).  0 disables both layouts.
SMALL_LANE_MAX_CHUNKS = int(os.environ.get("REPRO_SMALL_LANE_CHUNKS", "2"))
_BATCH_MIN_LANES = 3  # fewer small lanes than this stay on the flat path
_BATCH_MAX_PER_SHARD = 4  # fork/join cliff (measured; see above)
# Batched-SCOUT small-lane window: same shape as the static window but OFF
# by default — the batched scout runner loses on CPU at every measured
# width (B=4: 131us, B=8: 188us per lane-step vs 11.5us flat on the same
# workload; EXPERIMENTS.md scout A/B table).  Unlike the static step, a
# scout DFS decision is O(1) scalar work flat (four port probes compiled
# to straight-line code) but O(L_pad + 4*N_pad) one-hot vector work per
# lane batched — ~1.8us/lane-decision, linear in B with no amortization —
# and the lockstep retry loop runs max-iterations-over-B, so batching
# multiplies the inflated work by the slowest lane's divergence.  The
# window stays as an opt-in (env below / occupancy profile) for
# accelerator-shaped hosts where the one-hot rows are lane-parallel and
# it is the serial gathers that are catastrophic.
_BSCOUT_MAX_PER_SHARD = int(os.environ.get("REPRO_BSCOUT_PER_SHARD", "0"))
_STACK_MAX_K = 16  # lanes executed sequentially per shard, at most

# ---- planner cost-model weights (ordering heuristics only) ---------------
# Measured, replacing the former 3x-compile / 4x-step guesses (EXPERIMENTS
# "Scout lane layouts", measurement scripts quoted there).  Step weight:
# warm quick-preset group records (bench.PERF) put flat scout lanes at
# ~37.7us/step vs ~3.4us/step static.  Compile weights: cold
# ensure_compiled() wall on the quick preset's 8x8 geometry, cap 1024 —
# lane 1.9s static / 3.4s scout, stack 2.6/4.0, batched 2.8, bscout 5.3.
# Relative weights, not seconds: a mis-estimate only reorders the
# compile/dispatch queues.
_COST_SCOUT_STEP = 11.0  # scout scan step vs static step (37.7 / 3.4)
_COST_SCOUT_COMPILE = 1.7  # scout program compile vs static (3.4 / 1.9)
_COST_MULTILANE_COMPILE = 1.4  # stack/batched compile vs lane (2.6 / 1.9)

# ---- planner backend profile (DESIGN.md §2.2, Pallas lane layouts) -------
# "cpu" is the layout above: one unbatched lane per host core, batching
# only inside the measured small-lane window.  On an accelerator that
# inverts — one device wants thousands of batched lanes, and the CPU
# fork/join cliff does not exist — so the "occupancy" profile pools
# statically-routed lanes by occupancy (lanes x padded scan chunks per
# device, budget below) instead of core count and dispatches them through
# the batched runner (Pallas lane kernel when the lane backend says so).
# "auto" picks occupancy on GPU/TPU and cpu otherwise, which keeps the
# CPU profile — and every figure output — byte-identical by default.
# Scout pools follow the same split (ISSUE 10): occupancy-cut batched
# scout groups (``sim._make_batched_scout_step``) under "occupancy"; the
# cpu profile keeps the measured flat/stacked scout layout (its batched
# small-lane window is opt-in via REPRO_BSCOUT_PER_SHARD — off by
# default because it loses on CPU, see _BSCOUT_MAX_PER_SHARD above).
PLANNER_PROFILE = os.environ.get("REPRO_PLANNER_PROFILE", "auto")
_PROFILES = ("cpu", "occupancy", "auto")

# occupancy budget: padded scan chunks (lanes x chunks) a single device
# should carry per dispatch before the planner cuts a new group
OCCUPANCY_CHUNKS = int(os.environ.get("REPRO_OCCUPANCY_CHUNKS", "4096"))


def planner_profile() -> str:
    """Resolve PLANNER_PROFILE to "cpu" or "occupancy" for this process."""
    p = PLANNER_PROFILE
    if p not in _PROFILES:
        raise ValueError(f"unknown planner profile {p!r}; pick from {_PROFILES}")
    if p != "auto":
        return p
    import jax

    return "occupancy" if jax.default_backend() in S._ACCEL_BACKENDS else "cpu"

# background compile pool for the overlapped compile/execute pipeline: on
# an n-core host, n-1 workers compile while the main thread dispatches
# already-compiled groups (XLA compilation releases the GIL).
_COMPILE_POOL = None

# executable compiles/loads already in flight (cross-phase: ``precompile``
# submits a whole preset's worth before the first phase executes; the
# dispatch loop adopts the futures instead of resubmitting)
_INFLIGHT: dict = {}

# keys delegated to the out-of-process compile server (repro.ssd.xc_worker)
# and the server process handle.  Process mode needs the persistent store
# (the server publishes through it) and is the default when one is
# configured; REPRO_COMPILE_PROC=0 forces in-process threads.
_PROC_KEYS: set = set()
_PROC = None

# ---- self-healing compile backend (ISSUE 8) ------------------------------
# The compile server is a scheduling hint with no correctness surface, but
# a hint that HANGS (wedged process, SIGSTOP, swap death) used to cost the
# 600s poll deadline per delegated key.  A _ServerWatchdog built on the
# runtime fault-tolerance primitives closes that: the worker's heartbeat
# thread touches a file ~1/s, a silent worker past REPRO_XC_WATCHDOG_S is
# declared dead, and an alive-but-pathologically-slow worker is abandoned
# by the straggler rule.  Either way every delegated key falls back to the
# in-process compile path and the run completes — counted in
# ``bench.PERF["xc_watchdog_trips"/"xc_watchdog_fallbacks"]``.
_WATCHDOG_TIMEOUT_S = float(os.environ.get("REPRO_XC_WATCHDOG_S", "20.0"))
_WATCHDOG = None
_WD_LOCK = threading.Lock()


class _ServerWatchdog:
    """Liveness + progress tracking for one compile-server process.

    ``HeartbeatMonitor`` consumes the worker's heartbeat file (mtime
    changes become beats); ``StragglerDetector`` watches the wait time of
    each delegated key relative to the median wait of the keys currently
    being awaited, so one wedged key among progressing ones is flagged
    after ``patience`` strikes even while heartbeats continue."""

    # straggler observations are taken at this cadence, not per 50ms poll
    # tick, so ``patience`` means "straggling for patience * period"
    OBSERVE_PERIOD_S = 5.0

    def __init__(self, hb_path: str, timeout_s: float = None, clock=None):
        from repro.runtime.fault_tolerance import (HeartbeatMonitor,
                                                   StragglerDetector)

        self.hb_path = hb_path
        self._clock = clock or time.monotonic
        self.mon = HeartbeatMonitor(
            ["xc_worker"],
            timeout_s=(_WATCHDOG_TIMEOUT_S if timeout_s is None
                       else timeout_s),
            clock=clock,
        )
        self.strag = StragglerDetector(k=4.0, deadline_floor_s=60.0,
                                       patience=3)
        self.waits: dict = {}  # key -> wait start (perf_counter)
        self._mtime = None
        self._next_observe = self._clock() + self.OBSERVE_PERIOD_S
        self.reason = None

    def track(self, key: tuple) -> None:
        with _WD_LOCK:
            self.waits[key] = time.perf_counter()

    def untrack(self, key: tuple) -> None:
        with _WD_LOCK:
            self.waits.pop(key, None)

    def healthy(self) -> bool:
        """Poll the heartbeat file + straggler clock; False once the
        server should be abandoned (sticky)."""
        with _WD_LOCK:
            if self.reason is not None:
                return False
            try:
                m = os.path.getmtime(self.hb_path)
            except OSError:
                m = None
            if m is not None and m != self._mtime:
                self._mtime = m
                self.mon.beat("xc_worker")
            if self.mon.dead_hosts():
                self.reason = "heartbeat"
                return False
            now = self._clock()
            if now >= self._next_observe and self.waits:
                self._next_observe = now + self.OBSERVE_PERIOD_S
                t = time.perf_counter()
                durs = {str(k): t - t0 for k, t0 in self.waits.items()}
                if self.strag.observe_step(durs):
                    self.reason = "straggler"
                    return False
            return True


def _fail_server(reason: str) -> int:
    """Abandon the compile server: reclaim every delegated key for the
    in-process compile path.  Idempotent; returns reclaimed-key count."""
    global _PROC, _WATCHDOG
    with _WD_LOCK:
        n = len(_PROC_KEYS)
        if n == 0 and _PROC is None:
            return 0
        _PROC_KEYS.clear()
        proc, _PROC = _PROC, None
        _WATCHDOG = None
    if proc is not None and proc.poll() is None:
        try:
            proc.kill()
        except OSError:
            pass
    perf = bench.PERF
    perf["xc_watchdog_trips"] = perf.get("xc_watchdog_trips", 0) + 1
    perf["xc_watchdog_reason"] = reason
    obs_spans.instant("watchdog", "server_abandoned", reason=reason,
                      reclaimed_keys=n)
    return n


def _proc_mode() -> bool:
    """Compile on the out-of-process server?  Only with the AOT store
    opted into, and only on a CPU host: an accelerator belongs to one
    process, so a second process importing JAX there would fail or hang."""
    import jax

    return (exec_cache.cache_dir() is not None
            and os.environ.get("REPRO_COMPILE_PROC", "1") != "0"
            and jax.default_backend() not in S._ACCEL_BACKENDS)


def _proc_alive() -> bool:
    return _PROC is not None and _PROC.poll() is None


def _schedule_compiles(keys: list) -> None:
    """Route missing executables to the compile server (process mode) or
    the background thread pool."""
    keys = [k for k in keys
            if k not in S._EXEC_CACHE and k not in _INFLIGHT
            and k not in _PROC_KEYS and not exec_cache.has(k)]
    if not keys:
        return
    # keys arrive in need order (pool insertion follows run order, i.e.
    # phase order) — the compile stream publishes what the dispatcher
    # will ask for first
    if _proc_mode():
        global _PROC
        import subprocess
        import sys
        import tempfile

        # the first two programs gate the first phase, and nothing can
        # execute until they exist — compile them HERE, synchronously and
        # at full speed, while the server boots (its jax import alone is
        # ~3s) and works through the rest of the preset
        local, remote = keys[:2], keys[2:]
        if remote:
            global _WATCHDOG
            fd, path = tempfile.mkstemp(suffix=".xckeys")
            with os.fdopen(fd, "wb") as f:
                import pickle

                pickle.dump(remote, f)
            # heartbeat file: the worker's beat thread touches it ~1/s
            # from process start (before its jax import), the watchdog
            # turns mtime changes into HeartbeatMonitor beats
            hb_path = path + ".hb"
            with open(hb_path, "w"):
                pass
            env = dict(os.environ, REPRO_XC_HEARTBEAT=hb_path)
            _PROC = subprocess.Popen(
                [sys.executable, "-m", "repro.ssd.xc_worker", path],
                env=env,
            )
            _PROC_KEYS.update(remote)
            _WATCHDOG = _ServerWatchdog(hb_path)
            obs_spans.instant("compile", "xc_server_launched",
                              delegated_keys=len(remote))
        for k in local:
            S.ensure_compiled(k)
    else:
        for k in keys:
            _INFLIGHT[k] = _compile_pool().submit(S.ensure_compiled, k,
                                                  None)


def _await_server(key: tuple):
    """Poll-future body: wait for the compile server to publish ``key``,
    then load it; compile locally (in-process) if the server dies, hangs
    past the heartbeat deadline, or straggles — the watchdog abandons the
    server once, and every still-delegated key falls back immediately."""
    wd = _WATCHDOG
    if wd is not None:
        wd.track(key)
    tr = obs_spans.TRACER
    t_span = tr.now_us() if tr is not None else 0.0
    deadline = time.perf_counter() + 600.0
    try:
        while (_proc_alive() and not exec_cache.has(key)
               and time.perf_counter() < deadline):
            if wd is not None and not wd.healthy():
                _fail_server(wd.reason or "unhealthy")
                break
            time.sleep(0.05)
    finally:
        if wd is not None:
            wd.untrack(key)
    if not exec_cache.has(key):
        # the server never published this key — in-process fallback
        if _PROC is not None and not _proc_alive() and _PROC.returncode != 0:
            _fail_server("crashed")
        perf = bench.PERF
        perf["xc_watchdog_fallbacks"] = (
            perf.get("xc_watchdog_fallbacks", 0) + 1
        )
    if tr is not None:
        tr.complete("compile", "await_xc_server", t_span,
                    tr.now_us() - t_span)
    return S.ensure_compiled(key)


def _compile_pool():
    global _COMPILE_POOL
    if _COMPILE_POOL is None:
        # at least 2 workers even on a 2-core host: while the dispatcher
        # is starved (cold start of a phase) the cores should be running
        # two backend compiles, not one
        n = int(os.environ.get(
            "REPRO_COMPILE_WORKERS",
            str(min(4, max(2, (os.cpu_count() or 2) - 1))),
        ))
        _COMPILE_POOL = concurrent.futures.ThreadPoolExecutor(
            max_workers=max(1, n), thread_name_prefix="xc-compile",
        )
    return _COMPILE_POOL


def _pow2ceil(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


# fully-generic promotion tuple (every _PROMOTABLE scalar stays traced):
# the small-lane layouts trade per-step leanness for ONE executable per
# (geometry, capacity, layout) across every pool, phase and preset
_NO_PROMO = (None,) * len(S._PROMOTABLE)


@dataclasses.dataclass(frozen=True)
class RunRequest:
    """One pending ``bench.run_workload`` call, planned for batched
    execution."""

    name: str
    cfg: SSDConfig
    designs: tuple
    n_requests: int | None = None
    target_util: float | None = 1.5
    seed: int = 0


class _Lane:
    """One scan lane: a (run, design[, channel row]) unit of work."""

    __slots__ = ("run_idx", "design_idx", "seed", "tables_row", "txns",
                 "n", "pos", "spec", "out")

    def __init__(self, run_idx, design_idx, seed, tables_row, txns, n, pos,
                 spec):
        self.run_idx = run_idx
        self.design_idx = design_idx
        self.seed = seed
        self.tables_row = tables_row  # LaneTables row, numpy, no lane axis
        self.txns = txns  # TxnArrays, numpy, natural length n
        self.n = n
        self.pos = pos  # positions in the run's ordered space (None = all)
        self.spec = spec
        self.out = None  # StepOut numpy [capacity], filled by _run_pool

    @property
    def n_chunks(self) -> int:
        return -(-self.n // S.CHUNK)  # ceil; 0 chunks for an empty lane


def _want_decompose(flag, spec, confined: bool, cfg: SSDConfig, n: int,
                    rows_ok: bool) -> bool:
    if spec.kind == KIND_SCOUT or not confined or cfg.rows <= 1 or n == 0:
        return False
    if not rows_ok:  # txn row field inconsistent with node layout — safety
        return False
    if flag is True:
        return True
    return (flag == "auto"
            and n >= cfg.rows * AUTO_DECOMPOSE_MIN_CHUNKS_PER_ROW * S.CHUNK)


def _slice_txns(txns: S.TxnArrays, idx: np.ndarray) -> S.TxnArrays:
    return S.TxnArrays(*(a[idx] for a in txns))


def _pad_txns(txns: S.TxnArrays, cap: int) -> S.TxnArrays:
    out = []
    for a in txns:
        b = np.zeros((cap,), dtype=a.dtype)
        b[: len(a)] = a
        out.append(b)
    return S.TxnArrays(*out)


def _pool_promotions(lanes: list) -> tuple:
    """Common value of each promotable scalar across the POOL (not per
    group): every group of the pool must share one executable, so the
    specialization is computed once over all its lanes."""

    class _Stack:
        def __getattr__(self, name):
            return np.stack(
                [np.asarray(getattr(ln.tables_row, name)) for ln in lanes]
            )

    return S._promotions(_Stack())


@dataclasses.dataclass
class _GroupPlan:
    """One planned dispatch: a group of lanes bound to an executable key."""

    variant: str  # "lane" | "stack" | "batched" | "bscout"
    sig: tuple
    lanes: list  # dispatch order; may contain duplicate refs (padding)
    cap: int
    n_shards: int
    per_shard: int  # 1 (lane) | K (stack) | Bs (batched)
    k_max: int
    has_scout: bool
    fixed: tuple
    backend: str = "xla"  # lane-step kernel for "batched" plans
    key: tuple = None
    est_exec: float = 0.0
    est_compile: float = 0.0

    def finalize(self) -> "_GroupPlan":
        if self.variant == "lane":
            self.key = S.lane_group_key(self.sig, self.cap, len(self.lanes),
                                        self.k_max, self.has_scout,
                                        self.fixed, self.n_shards)
        elif self.variant == "stack":
            self.key = S.stack_group_key(self.sig, self.cap, self.per_shard,
                                         self.k_max, self.has_scout,
                                         self.fixed, self.n_shards)
        elif self.variant == "bscout":
            self.key = S.bscout_group_key(self.sig, self.cap,
                                          self.per_shard, self.k_max,
                                          self.fixed, self.n_shards,
                                          self.backend)
        else:
            self.key = S.batched_group_key(self.sig, self.cap,
                                           self.per_shard, self.fixed,
                                           self.n_shards, self.backend)
        # cost model (ordering heuristics only), measured from SpanTracer
        # plan->compile->dispatch spans on the quick preset (see
        # EXPERIMENTS.md "Planner cost model"): scout programs compile
        # slower than static ones (the nested scout while-loops) and a
        # scout step costs more than a static step; execute cost scales
        # with scheduled scan chunks
        w = _COST_SCOUT_STEP if self.has_scout else 1.0
        self.est_compile = (
            _COST_SCOUT_COMPILE if self.has_scout else 1.0
        ) * (_COST_MULTILANE_COMPILE if self.variant != "lane" else 1.0)
        self.est_exec = w * sum(ln.n_chunks for ln in self.lanes)
        return self


def _pad_block(block: list, size: int) -> list:
    block = list(block)
    while len(block) < size:
        block.append(block[-1])
    return block


def _plan_pool(sig: tuple, lanes: list, has_scout: bool) -> list:
    """Lay one (geometry, cost class) pool out as dispatchable groups,
    under the active planner backend profile (:func:`planner_profile`)."""
    if planner_profile() == "occupancy":
        return _plan_pool_occupancy(sig, lanes, has_scout)
    return _plan_pool_cpu(sig, lanes, has_scout)


def _plan_pool_occupancy(sig: tuple, lanes: list, has_scout: bool) -> list:
    """Accelerator layout for a pool: every lane runs in the batched
    runner — gather-free static step for statically-routed pools, the
    batched scout DFS runner (``sim._make_batched_scout_step``) for scout
    pools — grouped by occupancy: lanes x padded scan chunks per device,
    cut at OCCUPANCY_CHUNKS, rather than core count.  Lanes are
    length-sorted first, so a group's padded cost is its width times its
    longest (last) member and mixed-length pools don't pay a long lane's
    padding across every short one.  Bit-exact vs the cpu layout: both
    batched steps' masked-validity paths make the extra padding a no-op,
    pinned by tests/test_batched_pallas.py and tests/test_batched_scout.py.
    """
    n_shards = S.host_device_count()
    order = sorted(lanes, key=lambda ln: ln.n_chunks)
    cap = max(_CAP_SEEN.get(sig, 0), S._pad_to(max(ln.n for ln in order)))
    _CAP_SEEN[sig] = cap
    backend = S.resolve_lane_backend()
    k_max = (max(ln.spec.n_scouts for ln in lanes) if has_scout else 1)
    fixed = _pool_promotions(lanes) if has_scout else _NO_PROMO
    variant = "bscout" if has_scout else "batched"
    budget = max(1, OCCUPANCY_CHUNKS) * n_shards
    plans, i = [], 0
    while i < len(order):
        j = i + 1
        while (j < len(order)
               and (j - i + 1) * max(order[j].n_chunks, 1) <= budget):
            j += 1
        blk = order[i:j]
        i = j
        # a multiple of 8 lanes per device: the kernels tile the batch in
        # blocks of 8 TPU sublanes (``kernels.batched_step.lane_tile``)
        per = -(-len(blk) // (8 * n_shards)) * 8
        plans.append(_GroupPlan(
            variant, sig, _pad_block(blk, n_shards * per), cap,
            n_shards, per, k_max, has_scout, fixed, backend=backend,
        ))
    return [p.finalize() for p in plans]


def _plan_pool_cpu(sig: tuple, lanes: list, has_scout: bool) -> list:
    """The host-CPU layout of one (geometry, cost class) pool.

    Big lanes: one UNBATCHED lane per device shard, sorted by length (the
    sorted-length grouping keeps a group's barrier cheap).  Small lanes
    (<= SMALL_LANE_MAX_CHUNKS chunks): statically-routed ones collapse
    into the gather-free batched runner, scout ones stack K-per-shard —
    both cut the dispatch count of tiny-scan pools ~K/B-fold.  A pool
    smaller than the device count compiles at its own size; remainder
    blocks are padded with duplicate lanes (discarded outputs are cheaper
    than another executable).
    """
    n_shards = S.host_device_count()
    k_max = (max(ln.spec.n_scouts for ln in lanes) if has_scout else 1)
    fixed = _pool_promotions(lanes)
    order = sorted(lanes, key=lambda ln: ln.n_chunks)

    small_max = SMALL_LANE_MAX_CHUNKS
    small = [ln for ln in order if ln.n_chunks <= small_max]
    flat = [ln for ln in order if ln.n_chunks > small_max]
    plans = []
    # the small-lane window starts where the collapsed layouts save
    # dispatches over the flat path (> 2 per-lane groups' worth)
    if len(small) > 2 * n_shards and len(small) >= _BATCH_MIN_LANES:
        # small-lane layouts pad to their own (smaller) capacity
        # high-water, and run FULLY GENERIC programs (no promotions,
        # ``_NO_PROMO``): their total step count is tiny, so one
        # executable per (geometry, capacity, layout) serving every pool
        # beats a leaner program per promotion pattern — compile count is
        # the small-lane cost, not step cost
        skey = ("small", sig)
        scap = max(_CAP_SEEN.get(skey, 0),
                   S._pad_to(max(ln.n for ln in small)))
        _CAP_SEEN[skey] = scap
        if not has_scout and len(small) <= n_shards * _BATCH_MAX_PER_SHARD:
            Bs = -(-len(small) // n_shards)
            plans.append(_GroupPlan(
                "batched", sig, _pad_block(small, n_shards * Bs), scap,
                n_shards, Bs, 1, False, _NO_PROMO,
                backend=S.resolve_lane_backend(),
            ))
        elif has_scout and len(small) <= n_shards * _BSCOUT_MAX_PER_SHARD:
            # the batched-scout analogue of the static window: one
            # gather-free scout dispatch instead of K-per-shard lax.map
            # stacks.  Like every small-lane layout it runs the fully
            # generic program (``_NO_PROMO`` — hold/allow/n_scouts stay
            # traced per lane) so one executable per (geometry, capacity,
            # k_max) serves every pool.
            Bs = -(-len(small) // n_shards)
            plans.append(_GroupPlan(
                "bscout", sig, _pad_block(small, n_shards * Bs), scap,
                n_shards, Bs, k_max, True, _NO_PROMO,
                backend=S.resolve_lane_backend(),
            ))
        else:
            # one K for the whole pool, snapped to the {4, 16} ladder:
            # K fragments the executable key, and duplicate-lane padding
            # of tiny scans is far cheaper than another compile
            K = _pow2ceil(-(-len(small) // n_shards))
            K = 4 if K <= 4 else _STACK_MAX_K
            for i in range(0, len(small), n_shards * K):
                blk = small[i: i + n_shards * K]
                plans.append(_GroupPlan(
                    "stack", sig, _pad_block(blk, n_shards * K), scap,
                    n_shards, K, k_max, has_scout, _NO_PROMO,
                ))
    else:
        flat = order

    if flat:
        cap = max(_CAP_SEEN.get(sig, 0),
                  S._pad_to(max(ln.n for ln in flat)))
        _CAP_SEEN[sig] = cap
        G = max(1, min(n_shards, len(flat)))
        for i in range(0, len(flat), G):
            plans.append(_GroupPlan(
                "lane", sig, _pad_block(flat[i: i + G], G), cap,
                min(G, n_shards), 1, k_max, has_scout, fixed,
            ))
        if G < n_shards:
            # opportunistic width padding: a pool smaller than the device
            # count compiles at its own size UNLESS the full-width
            # executable already exists (memory or store) — duplicate
            # lanes run on otherwise-idle shards, so reusing the wide
            # program is free and saves the narrow compile
            p = plans[-1]
            wide = dataclasses.replace(
                p, lanes=_pad_block(p.lanes, n_shards),
                n_shards=n_shards,
            ).finalize()
            if wide.key in S._EXEC_CACHE or exec_cache.has(wide.key):
                plans[-1] = wide
    return [p.finalize() for p in plans]


def _dispatch(plan: _GroupPlan) -> dict:
    """Stack one plan's arguments, execute it, and scatter lane outputs.

    Packing (``pack_s``) runs from here until ``sim._run_compiled`` is
    entered (``t_pack``); unpacking (``unpack_s``) from the runner's
    return."""
    t_pack = time.perf_counter()
    lanes, cap = plan.lanes, plan.cap
    if plan.variant in ("lane", "stack"):
        tables = LaneTables(
            *(np.stack([np.asarray(getattr(ln.tables_row, f))
                        for ln in lanes])
              for f in LaneTables._fields)
        )
        seeds = np.asarray([ln.seed for ln in lanes], np.uint32)
        txns = S.TxnArrays(
            *(np.stack(cols) for cols in
              zip(*(_pad_txns(ln.txns, cap) for ln in lanes)))
        )
        ncs = np.asarray([ln.n_chunks for ln in lanes], np.int32)
        outs, perf = S.run_group(
            plan.sig, tables, seeds, txns, ncs, plan.k_max,
            plan.has_scout, plan.fixed, plan.n_shards,
            K=(plan.per_shard if plan.variant == "stack" else 0),
            t_pack=t_pack,
        )
        lane_axis = 0
    elif plan.variant == "bscout":
        B = len(lanes)
        scal = S.ScoutBatchScalars(
            *(np.asarray([np.asarray(getattr(ln.tables_row, name))
                          for ln in lanes])
              for name in S._PROMOTABLE),
            fc_valid=np.stack([np.asarray(ln.tables_row.fc_valid)
                               for ln in lanes]),
            fc_node=np.stack([np.asarray(ln.tables_row.fc_node)
                              for ln in lanes]),
            res_dead=np.stack([np.asarray(ln.tables_row.res_dead)
                               for ln in lanes]),
        )
        seeds = np.asarray([ln.seed for ln in lanes], np.uint32)
        txns = S.TxnArrays(*(
            np.stack([np.asarray(a) for a in cols], axis=1)
            for cols in zip(*(_pad_txns(ln.txns, cap) for ln in lanes))
        ))
        F0 = np.asarray(lanes[0].tables_row.fc_valid).shape[0]
        tt = S.ScoutBatchTxnTables(
            dist=np.zeros((cap, B, F0), np.int32),
        )
        done = {}
        for j, ln in enumerate(lanes):
            key = id(ln)
            if key not in done:  # dup padding lanes share the pregather
                done[key] = pregather_scout_tables(
                    ln.tables_row, np.asarray(ln.txns.node)
                )
            tt.dist[:ln.n, j] = done[key]["dist"]
        ncs = np.asarray([ln.n_chunks for ln in lanes], np.int32)
        outs, perf = S.run_batched_scout_group(
            plan.sig, scal, seeds, txns, tt, ncs, plan.k_max,
            plan.fixed, plan.n_shards, plan.per_shard, plan.backend,
            t_pack=t_pack,
        )
        lane_axis = 1
    else:
        B = len(lanes)
        scal = S.BatchScalars(
            *(np.asarray([np.asarray(getattr(ln.tables_row, name))
                          for ln in lanes])
              for name in S._PROMOTABLE),
            fc_valid=np.stack([np.asarray(ln.tables_row.fc_valid)
                               for ln in lanes]),
            res_dead=np.stack([np.asarray(ln.tables_row.res_dead)
                               for ln in lanes]),
        )
        txns = S.TxnArrays(*(
            np.stack([np.asarray(a) for a in cols], axis=1)
            for cols in zip(*(_pad_txns(ln.txns, cap) for ln in lanes))
        ))
        F0 = np.asarray(lanes[0].tables_row.fc_valid).shape[0]
        R = np.asarray(lanes[0].tables_row.cmask).shape[-1]
        W = mask_words_per_row(R)
        bt = S.BatchTxnTables(
            mask_words=np.zeros((cap, B, F0, 2, W), np.int32),
            hops=np.zeros((cap, B, F0, 2), np.int32),
            dist=np.zeros((cap, B, F0), np.int32),
            cand2=np.zeros((cap, B), bool),
            fc_fixed=np.zeros((cap, B, 2), np.int32),
        )
        done = {}
        for j, ln in enumerate(lanes):
            key = id(ln)
            if key not in done:  # dup padding lanes share the pregather
                done[key] = pregather_node_tables(
                    ln.tables_row, np.asarray(ln.txns.node)
                )
            pg = done[key]
            n = ln.n
            bt.mask_words[:n, j] = pg["mask_words"]
            bt.hops[:n, j] = pg["hops"]
            bt.dist[:n, j] = pg["dist"]
            bt.cand2[:n, j] = pg["cand2"]
            bt.fc_fixed[:n, j] = pg["fc_fixed"]
        ncs = np.asarray([ln.n_chunks for ln in lanes], np.int32)
        outs, perf = S.run_batched_group(plan.sig, scal, txns, bt, ncs,
                                         plan.fixed, plan.n_shards,
                                         plan.per_shard, plan.backend,
                                         t_pack=t_pack)
        lane_axis = 1
    with bench.stage("unpack_s", "unpack"):
        fields = [np.moveaxis(np.asarray(a), lane_axis, 0) for a in outs]
        seen = set()
        for j, ln in enumerate(lanes):
            if id(ln) in seen:  # padding duplicate — outputs discarded
                continue
            seen.add(id(ln))
            ln.out = S.StepOut(*(f[j] for f in fields))
    perf["lanes"] = len(seen)
    return perf


def _execute_plans(plans: list) -> list:
    """The overlapped compile/execute pipeline.

    Missing executables are resolved on the background pool — persistent-
    store loads and XLA backend compiles both release the GIL — while the
    main thread dispatches groups whose executables are ready.  The
    GIL-bound half of a compile (tracing + lowering) would fight the
    dispatching main thread for the interpreter, so it happens HERE, on
    the main thread, before the dispatch loop (``sim.lower_for_key``);
    keys the store already holds skip it entirely.  Orders are the cost
    model's: lowering/compile submission longest-compile-first (the
    cold-path critical path), dispatch longest-estimated-execute first
    (warm-path order: big groups keep the devices busy while stragglers'
    compiles finish).  Time the main thread spends with nothing
    dispatchable is ``compile_wait_s``; compile wall-clock hidden behind
    execution is the pipeline's win, ``compile_overlap_s``.
    """
    perf = bench.PERF
    c0 = perf.get("compile_s", 0.0)
    futures = {}
    for p in sorted(plans, key=lambda p: -p.est_compile):
        if p.key in futures or p.key in S._EXEC_CACHE:
            continue
        fut = _INFLIGHT.get(p.key)
        if fut is None:
            if p.key in _PROC_KEYS and _proc_alive():
                # delegated to the compile server: poll for its entry
                fut = _compile_pool().submit(_await_server, p.key)
            else:
                lowered = (None if exec_cache.has(p.key)
                           else S.lower_for_key(p.key))
                fut = _compile_pool().submit(S.ensure_compiled, p.key,
                                             lowered)
            _INFLIGHT[p.key] = fut
        futures[p.key] = fut
    pending = sorted(plans, key=lambda p: -p.est_exec)
    compile_recs = {}  # key -> [seconds, source], claimed by first group
    wait_s = 0.0
    perf_groups = []
    while pending:
        ready = [p for p in pending
                 if p.key not in futures or futures[p.key].done()]
        if not ready:
            t0 = time.perf_counter()
            with obs_spans.span("dispatch", "compile_stall",
                                pending=len(pending)):
                concurrent.futures.wait(
                    {futures[p.key] for p in pending if p.key in futures},
                    return_when=concurrent.futures.FIRST_COMPLETED,
                )
            wait_s += time.perf_counter() - t0
            continue
        p = ready[0]
        pending.remove(p)
        if p.key in futures and p.key not in compile_recs:
            _, dt, src = futures[p.key].result()
            compile_recs[p.key] = [dt, src]
            _INFLIGHT.pop(p.key, None)
        with obs_spans.span("dispatch", f"group:{p.variant}",
                            lanes=len(p.lanes), shards=p.n_shards,
                            capacity=p.cap):
            g = _dispatch(p)
        rec = compile_recs.get(p.key)
        if rec is not None and rec[1] != "claimed":
            dt, src = rec
            g["cache"] = src
            if src == "build":
                g["compile_s"] = round(dt, 3)
            elif src == "disk":
                g["load_s"] = round(dt, 3)
            rec[1] = "claimed"
        perf_groups.append(g)
    # attribute the pipeline: compile wall-clock that accrued during this
    # dispatch pass vs the time the main thread actually stalled on it
    # (approximate across phase boundaries — background compiles span them)
    total_compile = perf.get("compile_s", 0.0) - c0
    perf["compile_wait_s"] = perf.get("compile_wait_s", 0.0) + wait_s
    perf["compile_overlap_s"] = (
        perf.get("compile_overlap_s", 0.0)
        + max(0.0, total_compile - wait_s)
    )
    return perf_groups


def _lower_runs(runs: list) -> tuple:
    """Lower runs to lanes pooled by (geometry, cost class).

    Returns ``(prepared, pools)`` — ``prepared`` holds per-run
    ``(cfg, txns, designs, order, op, n)`` for result assembly, ``pools``
    maps ``(sig, scout)`` to its :class:`_Lane` list.

    A run may carry an optional sixth element, a ``designs.FaultSpec``:
    its hardware faults lower into the lane tables (``res_dead`` rides as
    a table argument, so faulted and fault-free lanes share executables)
    and its read-retry ladder stretches the packed op ticks."""
    prepared = []
    pools: dict = {}
    for run_idx, run in enumerate(runs):
        cfg, txns, designs, seeds, decompose = run[:5]
        faults = run[5] if len(run) > 5 else None
        designs = tuple(designs)
        specs = resolve_specs(designs)
        order = S._nominal_order(cfg, txns)
        n = len(order)
        packed, op = S._pack_txns(cfg, txns, order, faults)
        prepared.append((cfg, txns, designs, order, op, n))
        confined = rows_confined(cfg, designs)
        tables = lower_designs(cfg, designs, faults)
        rows_np = np.asarray(packed.row)
        rows_ok = bool(
            np.array_equal(rows_np, np.asarray(packed.node) // cfg.cols)
        )
        row_pos = None
        sig = S._geom_sig(cfg)
        for i, spec in enumerate(specs):
            tables_row = LaneTables(
                *(np.asarray(a)[i] for a in tables)
            )
            seed = seeds[i] | 1
            scout = spec.kind == KIND_SCOUT
            key = (sig, scout)
            dec = _want_decompose(decompose, spec, confined[i], cfg, n,
                                  rows_ok)
            if dec and row_pos is None:
                row_pos = [np.flatnonzero(rows_np == r)
                           for r in range(cfg.rows)]
            lane_list = pools.setdefault(key, [])
            if dec:
                for pos in row_pos:
                    if len(pos) == 0:
                        continue
                    lane_list.append(_Lane(
                        run_idx, i, seed, tables_row,
                        _slice_txns(packed, pos), len(pos), pos, spec,
                    ))
            else:
                lane_list.append(_Lane(
                    run_idx, i, seed, tables_row, packed, n, None, spec,
                ))
    return prepared, pools


def execute_sim_runs(runs: Sequence[tuple]) -> list:
    """Execute many sweeps as pooled, sharded lane groups.

    ``runs``: iterable of ``(cfg, txns, designs, seeds, decompose)`` —
    ``seeds`` a per-lane tuple — optionally extended with a sixth
    element, a ``designs.FaultSpec`` to inject hardware faults into that
    run's lanes.  Returns per-run lists of
    :class:`~repro.ssd.sim.SimResult`, each bit-identical to
    ``sim.simulate`` of that lane alone.
    """
    runs = list(runs)
    with bench.stage("lower_s", "lower"):
        prepared, pools = _lower_runs(runs)
        plans = []
        for (sig, scout), lanes in pools.items():
            plans.extend(_plan_pool(sig, lanes, scout))
    all_groups = _execute_plans(plans)

    # ---- PERF accounting (bench.PERF is the process-wide scoreboard) ----
    perf = bench.PERF
    if all_groups:  # devices actually used, not merely available
        perf["devices_used"] = max(perf.get("devices_used", 0),
                                   max(g["shards"] for g in all_groups))
    # compile_s / xc_load_s accumulate inside ``sim.ensure_compiled`` (a
    # background compile counts even if it finishes before any group
    # adopts its future); groups carry per-group attribution only
    for g in all_groups:
        perf["lanes"] = perf.get("lanes", 0) + g["lanes"]
        perf["scan_steps_padded"] = (
            perf.get("scan_steps_padded", 0) + g["steps"]
        )
        perf["exec_s"] = perf.get("exec_s", 0.0) + g["exec_s"]
    perf.setdefault("groups", []).extend(all_groups)
    # mirror the persistent-store telemetry (absolute, process-wide)
    for k, v in exec_cache.STATS.items():
        perf[f"xc_{k}"] = v
    with bench.stage("unpack_s", "unpack"):
        return _merge_runs(runs, prepared, pools)


def _merge_runs(runs: list, prepared: list, pools: dict) -> list:
    """Merge lane outputs back into per-run SimResults."""
    perf = bench.PERF
    results: list = []
    by_run: dict = {}
    for lanes in pools.values():
        for ln in lanes:
            by_run.setdefault((ln.run_idx, ln.design_idx), []).append(ln)
    rec = obs_events.RECORDER
    for run_idx, (cfg, txns, designs, order, op, n) in enumerate(prepared):
        run_res = []
        for i, design in enumerate(designs):
            lanes = by_run[(run_idx, i)]
            perf["scan_steps_valid"] = (
                perf.get("scan_steps_valid", 0) + sum(ln.n for ln in lanes)
            )
            if len(lanes) == 1 and lanes[0].pos is None:
                outs = lanes[0].out
            else:  # channel-decomposed: scatter rows back to ordered space
                outs = S.StepOut(*(
                    np.zeros((n,), dtype=np.asarray(f).dtype)
                    for f in lanes[0].out
                ))
                for ln in lanes:
                    for dst, src in zip(outs, ln.out):
                        dst[ln.pos] = src[: ln.n]
            run_res.append(
                S._finish_result(cfg, design, txns, order, op, outs, n)
            )
            if rec is not None:
                # flight recorder: same ingredients as _finish_result —
                # purely host-side, the scan carried nothing extra
                run_in = runs[run_idx]
                if len(run_in) > 5 and run_in[5] is not None:
                    rec.record_fault_swap(design, 0, lanes[0].tables_row,
                                          cfg.rows * cfg.cols)
                rec.record_run(
                    cfg, design, txns, order, op, outs, n,
                    lanes[0].tables_row,
                    lanes[0].spec.kind == KIND_SCOUT,
                    label=f"run{run_idx}",
                )
        results.append(run_res)
    return results


# ids of planned batches (``execute_requests`` calls), for their spans
_BATCH_IDS = itertools.count(1)


def _request_key(rq: RunRequest) -> tuple:
    return (rq.name, rq.cfg, rq.designs, rq.n_requests, rq.target_util,
            rq.seed)


def _sims_for(requests: Sequence[RunRequest]) -> tuple:
    """Trace + decompose a request batch into planner runs.

    Returns ``(sims, meta)`` with ``sims`` the ``execute_sim_runs`` input
    and ``meta`` per-request ``(accel, txns)``.  Decompositions go through
    the content-keyed LRU, so ``precompile`` and the phase body share one
    pass."""
    from repro.traces.generator import default_n_requests, to_pages, trace_for

    sims, meta = [], []
    for rq in requests:
        n_req = rq.n_requests or default_n_requests(rq.name)
        with obs_spans.span("plan", "trace"):
            trace = trace_for(rq.name, n_req, rq.seed)
            accel = 1.0
            offered = bench.offered_utilization(trace, rq.cfg)
            if rq.target_util is not None:
                trace, accel = bench.accelerate(trace, rq.cfg,
                                                rq.target_util)
            bench.record_accel(rq.name, rq.cfg, accel, offered,
                               rq.target_util)
            pages = to_pages(trace, rq.cfg.page_bytes)
        with bench.stage("ftl_s", "ftl"):
            txns = bench.decompose_cached(rq.cfg, pages,
                                          int(pages["footprint_pages"]))
        seeds = ((rq.seed + 7),) * len(rq.designs)
        sims.append((rq.cfg, txns, rq.designs, seeds, "auto"))
        meta.append((accel, txns))
    return sims, meta


def execute_requests(requests: Sequence[RunRequest]) -> list:
    """Trace + decompose + simulate a batch of workload requests as one
    planned execution; results are inserted into ``bench._RUN_CACHE`` under
    the exact keys ``bench.run_workload`` uses.  Every program span of the
    batch carries its ``batch`` id."""
    with obs_spans.batch(next(_BATCH_IDS)):
        sims, meta = _sims_for(requests)
        t0 = time.perf_counter()
        all_results = execute_sim_runs(sims)
        bench.PERF["sim_s"] += time.perf_counter() - t0
    out = []
    # a prefetched phase reads the whole batch back AFTER this returns, so
    # the batch must survive in the LRU together — insert with a cap at
    # least the batch size (later normal-cap inserts shrink the cache back
    # down, so this pins the batch without permanently growing the cap)
    cap = max(bench._RUN_CACHE_MAX, len(requests))
    for rq, (accel, txns), results in zip(requests, meta, all_results):
        run = bench.WorkloadRun(
            name=rq.name, cfg=rq.cfg, accel=accel,
            n_requests=txns.n_requests,
            results=dict(zip(rq.designs, results)),
            origin_phase=bench.PERF.get("phase"),
        )
        bench._lru_put(bench._RUN_CACHE, _request_key(rq), run, cap)
        out.append(run)
    return out


def prefetch(requests: Sequence[RunRequest]) -> None:
    """Plan and execute every not-yet-cached request as one batch.

    A figure phase calls this with its whole (workload, config) list; the
    phase body's ``run_workload`` calls are then all served from the run
    cache, so the phase's sweeps execute as pooled sharded groups instead
    of one eager sweep per workload."""
    pending, seen = [], set()
    for rq in requests:
        key = _request_key(rq)
        if key in seen:
            continue
        seen.add(key)
        # silent probe: planned work is counted as ``run_prefetched`` so
        # the hit/miss telemetry keeps meaning "work avoided/incurred by a
        # run_workload call" (the phase body's hits on prefetched entries
        # are real cache hits — the plan warmed them)
        if bench._cached_run(*key, count=False) is None:
            pending.append(rq)
    if pending:
        bench.PERF["run_prefetched"] += len(pending)
        execute_requests(pending)


def precompile(requests: Sequence[RunRequest],
               extra_keys: Sequence[tuple] = ()) -> None:
    """Plan a request batch WITHOUT executing it and start compiling every
    missing executable — on the out-of-process compile server when the
    persistent store is configured (in-process background compilation
    measured a ~2.3x GIL/core-contention tax on small hosts), else on the
    background thread pool.

    The cross-phase half of the overlapped pipeline: ``benchmarks/run.py``
    hands the whole preset over before the first phase runs, so a late
    phase's programs (fig15's fresh geometries, the tail's small-lane
    layouts via ``extra_keys``) compile while early phases execute.  Costs
    one planning pass (decompositions land in the shared LRU the phases
    reuse); dispatch later adopts in-flight futures / published store
    entries.  Purely a scheduling hint — a wrong or stale hint only means
    the compile happens at first use, as without it."""
    pending, seen = [], set()
    for rq in requests:
        key = _request_key(rq)
        if key in seen:
            continue
        seen.add(key)
        if bench._cached_run(*key, count=False) is None:
            pending.append(rq)
    plans = []
    if pending:
        sims, _ = _sims_for(pending)
        _, pools = _lower_runs(sims)
        for (sig, scout), lanes in pools.items():
            plans.extend(_plan_pool(sig, lanes, scout))
    keys = [p.key for p in plans] + list(extra_keys)
    if keys:
        _schedule_compiles(keys)


def prewarm_small_keys(cfg: SSDConfig, n_hint: int,
                       k_max: int = 1) -> list:
    """Executable keys of the generic small-lane layout programs a QoS
    phase will predictably need (static stack, scout stack) for lanes of
    roughly ``n_hint`` transactions — feed to :func:`precompile` as
    ``extra_keys``.  A hint, not a commitment."""
    sig = S._geom_sig(cfg)
    ns = S.host_device_count()
    cap = max(_CAP_SEEN.get(("small", sig), 0), S._pad_to(n_hint))
    return [
        S.stack_group_key(sig, cap, _STACK_MAX_K, 1, False, _NO_PROMO, ns),
        S.stack_group_key(sig, cap, 4, k_max, True, _NO_PROMO, ns),
    ]
