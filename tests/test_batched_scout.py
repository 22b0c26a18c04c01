"""Batched scout lanes (ISSUE 10): the gather-free scout DFS runner.

PR 5's batched runner stopped at statically-routed designs; this PR steps
[B] scout DFS machines per dispatch (``sim._make_batched_scout_step`` +
``kernels.ops.route_dfs``) with each lane routing against its own
link-occupancy map.  The parity bar is the house rule: element-wise
bit-identical to the flat per-lane scan AND to ``scalar_ref`` for every
scout design — rng streams, retry schedules and the k-scout race
included — with and without injected faults, on the XLA step and the
promoted Pallas kernel (interpreter mode, so CI needs no accelerator).
The planner's layout choice is pure policy; these tests force the bscout
layouts regardless of the measured thresholds in ``sweep_plan``.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.ssd import DESIGNS, bench, decompose_trace, simulate
from repro.ssd import sim as S
from repro.ssd import sweep_plan as SP
from repro.ssd.designs import REGISTRY, KIND_SCOUT, FaultSpec
from repro.ssd.scalar_ref import simulate_ref
from repro.traces.generator import gen_trace, to_pages

PARITY_FIELDS = ("completion", "wait", "conflict", "hops", "tries",
                 "misroutes")
SCOUT_DESIGNS = tuple(d for d in DESIGNS
                      if REGISTRY[d].kind == KIND_SCOUT)

FAULT_SPECS = {
    "none": None,
    "link": FaultSpec(failed_links=(0,)),
    "link+fc": FaultSpec(failed_links=(0,), failed_fcs=(1,)),
    "router": FaultSpec(failed_routers=(3,)),
}


def _assert_parity(lane, solo, ctx):
    for f in PARITY_FIELDS:
        assert np.array_equal(np.asarray(getattr(lane, f)),
                              np.asarray(getattr(solo, f))), (ctx, f)
    if lane.failed is not None or solo.failed is not None:
        assert np.array_equal(np.asarray(lane.failed),
                              np.asarray(solo.failed)), (ctx, "failed")
    assert lane.bus_hold_ticks == solo.bus_hold_ticks, ctx
    assert lane.link_hold_ticks == solo.link_hold_ticks, ctx


def _force_bscout(monkeypatch, backend="xla"):
    """Every scout pool lands in ONE batched scout dispatch."""
    monkeypatch.setattr(SP, "SMALL_LANE_MAX_CHUNKS", 64)
    monkeypatch.setattr(SP, "_BATCH_MIN_LANES", 2)
    monkeypatch.setattr(SP, "_BSCOUT_MAX_PER_SHARD", 64)
    monkeypatch.setattr(S, "LANE_BACKEND", backend)


@pytest.mark.parametrize("backend", ["xla", "pallas-interpret"])
def test_bscout_every_scout_design(tiny_cfg, tiny_txns, monkeypatch,
                                   backend):
    """One batched scout dispatch spanning ALL scout designs
    (heterogeneous hold/allow/n_scouts in one pool) == per-design flat
    ``simulate``, bit for bit, on both lane-step backends."""
    _force_bscout(monkeypatch, backend)
    designs = SCOUT_DESIGNS * 2  # wider than the 2*n_shards window
    g0 = len(bench.PERF["groups"])
    sweep = S.simulate_sweep(tiny_cfg, tiny_txns, designs, seeds=9,
                             decompose=False)
    new = bench.PERF["groups"][g0:]
    assert {g["variant"] for g in new} == {"bscout"}
    assert len(new) == 1  # the whole scout sweep was ONE dispatch
    for lane, design in zip(sweep, designs):
        _assert_parity(lane, simulate(tiny_cfg, tiny_txns, design, seed=9),
                       (backend, design))


@pytest.mark.parametrize("spec_name", sorted(FAULT_SPECS))
def test_bscout_faults_res_dead(tiny_cfg, tiny_txns, monkeypatch,
                                spec_name):
    """``res_dead`` fault masks flow through the batched scout path: dead
    links/FCs look permanently busy to every lane's DFS and the failed
    surface (FAIL_TIMEOUT rows) matches the flat oracle exactly."""
    _force_bscout(monkeypatch)
    spec = FAULT_SPECS[spec_name]
    designs = SCOUT_DESIGNS * 2
    g0 = len(bench.PERF["groups"])
    sweep = S.simulate_sweep(tiny_cfg, tiny_txns, designs, seeds=4,
                             decompose=False, faults=spec)
    assert "bscout" in {g["variant"]
                        for g in bench.PERF["groups"][g0:]}
    for lane, design in zip(sweep, designs):
        _assert_parity(
            lane, simulate(tiny_cfg, tiny_txns, design, seed=4,
                           faults=spec), (spec_name, design))


@pytest.fixture(scope="module")
def two_die(tiny_cfg):
    """The tiny mesh with two dies per package: pages stripe over dies."""
    cfg = dataclasses.replace(tiny_cfg, dies_per_chip=2)
    tr = dict(gen_trace("src2_1", 60, seed=3))
    tr["arrival_us"] = tr["arrival_us"] / 16.0
    pages = to_pages(tr, cfg.page_bytes)
    txns = decompose_trace(cfg, pages,
                           footprint_pages=int(pages["footprint_pages"]))
    assert len(np.unique(np.asarray(txns["plane"]) // cfg.planes_per_die
                         % cfg.dies_per_chip)) == 2  # both dies carry pages
    return cfg, txns


@pytest.mark.parametrize("dies", [1, 2])
@pytest.mark.parametrize("design", SCOUT_DESIGNS)
def test_bscout_scalar_ref_parity(tiny_cfg, tiny_txns, two_die, monkeypatch,
                                  design, dies):
    """The batched path also matches the independent scalar reference —
    same parity bar the flat scan is held to (seeds go through the same
    ``seed | 1`` lane transform on both sides) — with one die per package
    and with two."""
    _force_bscout(monkeypatch)
    cfg, txns = (tiny_cfg, tiny_txns) if dies == 1 else two_die
    lanes = (design,) * 6
    sweep = S.simulate_sweep(cfg, txns, lanes, seeds=(7,) * 6,
                             decompose=False)
    ref = simulate_ref(cfg, txns, design, seed=7)
    for lane in sweep:
        for f in PARITY_FIELDS:
            assert np.array_equal(np.asarray(getattr(lane, f)),
                                  ref[f]), (design, dies, f)


def test_bscout_kscout_race_masking(tiny_cfg, tiny_txns, monkeypatch):
    """Heterogeneous n_scouts in one pool (k_max=3): the 1-scout lanes
    must be masked out of the extra race rounds — bit-identical to their
    solo runs, rng stream included."""
    _force_bscout(monkeypatch)
    designs = ("venice", "venice_kscout", "venice_minimal", "venice_hold",
               "venice", "venice_kscout")
    sweep = S.simulate_sweep(tiny_cfg, tiny_txns, designs, seeds=9,
                             decompose=False)
    for lane, design in zip(sweep, designs):
        _assert_parity(lane, simulate(tiny_cfg, tiny_txns, design, seed=9),
                       design)


def test_bscout_mixed_lengths_masked_tail(tiny_cfg, tiny_txns,
                                          monkeypatch):
    """Scout lanes of different lengths share a batch: the shorter lane's
    masked tail steps must not perturb it (validity masking == the
    unbatched cond-skip), and its rng stream must not advance."""
    _force_bscout(monkeypatch)
    short = {k: np.asarray(v)[: len(tiny_txns["arrival"]) // 3]
             for k, v in dict(tiny_txns).items()}
    runs = [
        (tiny_cfg, tiny_txns, ("venice", "venice_kscout", "venice_hold"),
         (5, 5, 5), False),
        (tiny_cfg, short, ("venice", "venice_minimal"), (5, 5), False),
    ]
    res_long, res_short = SP.execute_sim_runs(runs)
    for res, design in zip(res_long, ("venice", "venice_kscout",
                                      "venice_hold")):
        _assert_parity(res, simulate(tiny_cfg, tiny_txns, design, seed=5),
                       ("long", design))
    for res, design in zip(res_short, ("venice", "venice_minimal")):
        _assert_parity(res, simulate(tiny_cfg, short, design, seed=5),
                       ("short", design))


def test_bscout_occupancy_profile(tiny_cfg, tiny_txns, monkeypatch):
    """Under the occupancy profile a scout pool dispatches as bscout
    occupancy groups (no monkeypatched windows) and stays bit-exact —
    the accelerator layout the CI A/B leg exercises."""
    monkeypatch.setattr(SP, "PLANNER_PROFILE", "occupancy")
    g0 = len(bench.PERF["groups"])
    sweep = S.simulate_sweep(tiny_cfg, tiny_txns, SCOUT_DESIGNS, seeds=11,
                             decompose=False)
    new = bench.PERF["groups"][g0:]
    assert {g["variant"] for g in new} == {"bscout"}
    for lane, design in zip(sweep, SCOUT_DESIGNS):
        _assert_parity(lane, simulate(tiny_cfg, tiny_txns, design,
                                      seed=11), design)


def test_bscout_telemetry_split(tiny_cfg, tiny_txns, monkeypatch):
    """Scout lane-steps land in the scout tallies (``steps_scout_*``),
    not the static ones — the kernel_dispatch split BENCH artifacts
    surface."""
    _force_bscout(monkeypatch)
    b0 = bench.PERF["steps_scout_batched"]
    s0 = bench.PERF["steps_batched"]
    S.simulate_sweep(tiny_cfg, tiny_txns, SCOUT_DESIGNS * 2, seeds=13,
                     decompose=False)
    assert bench.PERF["steps_scout_batched"] > b0
    assert bench.PERF["steps_batched"] == s0


@pytest.fixture(scope="module")
def congested():
    """A 4x4 mesh under a saturating trace with both links of the corner
    chip 15 dead: walks retry up to ~26 times, and every transaction to
    chip 15 fails after ``_MAX_TRIES`` tries, the last at ``max(free)``."""
    from repro.core.topology import build_mesh
    from repro.ssd import perf_optimized

    cfg = perf_optimized(rows=4, cols=4, pages_per_block=64)
    tr = dict(gen_trace("src2_1", 40, seed=3))
    tr["arrival_us"] = tr["arrival_us"] / 64.0
    pages = to_pages(tr, cfg.page_bytes)
    txns = decompose_trace(cfg, pages,
                           footprint_pages=int(pages["footprint_pages"]))
    dead = build_mesh(4, 4).port_link[15]
    spec = FaultSpec(failed_links=tuple(int(x) for x in dead if x >= 0))
    return cfg, txns, spec


@pytest.fixture(scope="module")
def by_slots(congested):
    """The four scout designs twice over in one batched scout group
    (k_max 3, 4 lanes a shard), with the runner built to walk one try a
    lane per call (``slots=1``: 4 slots), 8 tries per call, and the shape
    rule's slots: their results and scout counters."""
    cfg, txns, spec = congested
    out = {}
    for slots in (1, 8, None):
        with pytest.MonkeyPatch.context() as mp:
            _force_bscout(mp)
            make = S._make_batched_scout_step
            mp.setattr(S, "_make_batched_scout_step",
                       lambda *a, **k: make(*a, **k, slots=slots))
            S.clear_exec_cache()
            S._build_batched_scout_fn.cache_clear()
            keys = ("dfs_steps_live", "scout_walk_calls", "scout_tries_walked")
            p0 = {k: bench.PERF[k] for k in keys}
            g0 = len(bench.PERF["groups"])
            sweep = S.simulate_sweep(cfg, txns, SCOUT_DESIGNS * 2, seeds=4,
                                     decompose=False, faults=spec)
            groups = bench.PERF["groups"][g0:]
            assert [g["variant"] for g in groups] == ["bscout"]
            out[slots] = (sweep, {k: bench.PERF[k] - p0[k] for k in keys})
        S.clear_exec_cache()
        S._build_batched_scout_fn.cache_clear()
    return out


@pytest.fixture(scope="module")
def oracles(congested):
    """Each scout design on the congested trace: the flat scan's result
    and ``scalar_ref``'s outputs."""
    cfg, txns, spec = congested
    return {d: (simulate(cfg, txns, d, seed=4, faults=spec),
                simulate_ref(cfg, txns, d, seed=4, faults=spec))
            for d in SCOUT_DESIGNS}


def _n_slots(slots):
    """Try slots of a call in the ``by_slots`` group (4 lanes a shard)."""
    return max(4, slots or S.try_slots(4, 3, 128, 16))


@pytest.mark.parametrize("slots", [1, 8, None])
def test_speculative_tries_equal_the_flat_scan_and_the_reference(
        by_slots, oracles, slots):
    """Walking the next tries of every live lane side by side gives what
    the sequential retry loop gives, bit for bit: equal to the flat scan
    and to ``scalar_ref`` for every scout design, k-scout race and dead
    links included, with tries past a round's last slot and failures at
    ``_MAX_TRIES``.  ``None`` is the shape rule's slots (one walk tile at
    3 raced scouts a try)."""
    sweep, _ = by_slots[slots]
    n_slots = _n_slots(slots)
    for lane, design in zip(sweep, SCOUT_DESIGNS * 2):
        tries = np.asarray(lane.tries)
        assert (tries > n_slots).any(), design  # more tries than one call
        assert (tries == S._MAX_TRIES).any() and np.asarray(lane.failed).any()
        flat, ref = oracles[design]
        _assert_parity(lane, flat, (slots, design))
        for f in PARITY_FIELDS:
            assert np.array_equal(np.asarray(getattr(lane, f)), ref[f]), (
                slots, design, f)


def test_speculative_tries_keep_the_live_dfs_steps(by_slots):
    """``dfs_steps_live`` counts the walks the sequential schedule takes,
    whatever the tries walked per call; more per call make fewer calls,
    and every try taken was walked."""
    counts = [by_slots[s][1] for s in sorted((1, 8, None), key=_n_slots)]
    assert counts[0]["dfs_steps_live"] > 0
    assert len({c["dfs_steps_live"] for c in counts}) == 1
    calls = [c["scout_walk_calls"] for c in counts]
    assert calls[0] > calls[-1] and calls == sorted(calls, reverse=True)
    taken = sum(int(np.asarray(lane.tries).sum()) for lane in by_slots[1][0])
    assert all(c["scout_tries_walked"] >= taken for c in counts)


def test_try_slots_follow_the_batch_and_the_mesh():
    """A call walks as many tries as one walk tile holds, at least one a
    lane: the tile at k raced scouts a try, or the batch."""
    tile = S._TRIES_TILE
    assert S.try_slots(4, 3, 128, 16) == max(4, tile // 3)
    assert S.try_slots(16, 1, 128, 64) == max(16, tile)
    assert S.try_slots(16, 1, 256, 128) == max(16, tile)
    assert S.try_slots(4 * tile, 1, 128, 64) == 4 * tile


def test_try_times_follow_the_sequential_retry_rule():
    """The whole retry schedule at once equals the flat loop's try by try
    rule: the next link event after the last try, one tick later each
    once none is left, and the last try once every link is free."""
    rng = np.random.default_rng(5)
    B, L = 6, 40
    free = rng.integers(0, 60, (B, L)).astype(np.int32)  # many repeats
    gap_s = rng.integers(0, 60, (B, L)).astype(np.int32)
    gap_e = gap_s + 5
    t0 = np.array([0, 10, 30, 59, 70, 5], np.int32)
    got = np.asarray(S._try_times((free, gap_s, gap_e), t0))
    big = int(S._BIG)
    for b in range(B):
        t, want = int(t0[b]), [int(t0[b])]
        for i in range(1, S._MAX_TRIES):
            ev = min([int(v) for v in (*free[b], *gap_s[b]) if v > t],
                     default=big)
            t = max(ev, t + 1)
            if i + 1 >= S._MAX_TRIES:
                t = int(free[b].max())
            want.append(t)
        assert got[b].tolist() == want, b


def test_rng_advances_follow_the_one_step_rule():
    """All of a lane's rng advances at once equal the flat loop's step by
    step advance, from odd and from even states."""
    rng = np.array([1, 2, 7, 2**31, 2**32 - 1, 123456789], np.uint32)
    got = np.asarray(S._rng_advances(jnp.asarray(rng), 12))
    x = rng.astype(np.uint64)
    want = [x]
    for _ in range(12):
        x = ((x * 747796405 + 2891336453) % 2**32) | 1
        want.append(x)
    assert np.array_equal(got, np.stack(want, axis=1).astype(np.uint32))
