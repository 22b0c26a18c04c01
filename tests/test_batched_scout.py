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
