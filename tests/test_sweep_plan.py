"""Sweep planner: decomposed/sharded execution must be bit-exact.

The load-bearing guarantees of this PR's execution model:

* channel-decomposed scans (row-confined static lanes split by channel
  row) are bit-identical to the flat single-lane ``simulate``;
* the planner's pooled, sharded, chunk-trimmed groups — across designs,
  workloads AND geometries in one batch — are bit-identical too;
* the same holds in a single-device environment (subprocess probe, since
  the in-process suite runs with 2 forced host devices — see conftest);
* the vectorized ``_nominal_order`` grouped-cumsum pass matches the
  per-transaction reference loop exactly.
"""
import dataclasses
import hashlib
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.ssd import DESIGNS, bench, simulate, simulate_sweep
from repro.ssd import sim as S
from repro.ssd.designs import rows_confined
from repro.ssd.sweep_plan import execute_sim_runs

PARITY_FIELDS = ("completion", "wait", "conflict", "hops", "tries",
                 "misroutes")

CONFINED = ("baseline", "pssd", "ideal")


def _assert_lane_parity(lane, solo, ctx):
    for f in PARITY_FIELDS:
        assert np.array_equal(getattr(lane, f), getattr(solo, f)), (ctx, f)
    assert lane.exec_ticks == solo.exec_ticks, ctx
    assert lane.bus_hold_ticks == solo.bus_hold_ticks, ctx
    assert lane.link_hold_ticks == solo.link_hold_ticks, ctx


def test_rows_confined_is_proved_not_assumed(tiny_cfg):
    """The decomposition proof: private/row buses pass, anything that can
    couple rows (column buses, dynamic FC selection, the global-mesh
    scout) fails and falls back to the flat scan."""
    flags = dict(zip(DESIGNS, rows_confined(tiny_cfg, DESIGNS)))
    for d in CONFINED:
        assert flags[d], d
    for d in ("pnssd", "nossd", "venice", "venice_minimal", "venice_hold",
              "venice_kscout"):
        assert not flags[d], d


def test_channel_decomposed_parity_all_designs(tiny_cfg, tiny_txns):
    """decompose=True vs the flat 1-lane oracle, every registered design.

    Confined lanes actually decompose (asserted via the planner's lane
    accounting); unconfined lanes must fall back — both bit-exact."""
    lanes0 = bench.PERF["lanes"]
    sweep = simulate_sweep(tiny_cfg, tiny_txns, DESIGNS, seeds=5,
                           decompose=True)
    # 3 confined designs split into 2 rows each on the 2x2 mesh: the lane
    # count exceeds one-per-design (group padding may add duplicates)
    assert bench.PERF["lanes"] - lanes0 >= len(DESIGNS) + len(CONFINED)
    for lane, design in zip(sweep, DESIGNS):
        solo = simulate(tiny_cfg, tiny_txns, design, seed=5)
        _assert_lane_parity(lane, solo, design)


def test_planner_multi_run_mixed_geometry_parity(tiny_cfg, tiny_txns):
    """One planned batch spanning two geometries (2x2 and 2x3) and two
    design subsets must equal per-lane ``simulate`` on the right config."""
    from repro.ssd import decompose_trace
    from repro.traces.generator import gen_trace, to_pages

    cfg2 = dataclasses.replace(tiny_cfg, name="t2x3", cols=3)
    tr = gen_trace("hm_0", 40, seed=1)
    pages = to_pages(tr, cfg2.page_bytes)
    txns2 = decompose_trace(cfg2, pages,
                            footprint_pages=int(pages["footprint_pages"]))
    designs1 = ("baseline", "pnssd", "venice", "ideal")
    designs2 = ("baseline", "nossd", "venice_kscout")  # pnssd needs square
    runs = [
        (tiny_cfg, tiny_txns, designs1, (5,) * 4, "auto"),
        (cfg2, txns2, designs2, (9,) * 3, True),
    ]
    res1, res2 = execute_sim_runs(runs)
    for lane, design in zip(res1, designs1):
        _assert_lane_parity(lane, simulate(tiny_cfg, tiny_txns, design,
                                           seed=5), ("2x2", design))
    for lane, design in zip(res2, designs2):
        _assert_lane_parity(lane, simulate(cfg2, txns2, design, seed=9),
                            ("2x3", design))


def test_planner_perf_accounting(tiny_cfg, tiny_txns):
    """PERF must attribute the execution: lanes, trimmed step counts,
    devices, and a per-group compile-vs-execute split."""
    before = {k: bench.PERF[k] for k in
              ("lanes", "scan_steps_valid", "scan_steps_padded")}
    g0 = len(bench.PERF["groups"])
    simulate_sweep(tiny_cfg, tiny_txns, ("baseline", "venice"), seeds=3)
    assert bench.PERF["lanes"] > before["lanes"]
    dv = bench.PERF["scan_steps_valid"] - before["scan_steps_valid"]
    dp = bench.PERF["scan_steps_padded"] - before["scan_steps_padded"]
    n = len(tiny_txns["arrival"])
    assert dv >= 2 * n  # both lanes' valid steps counted
    assert dp >= dv  # padded counts chunk round-up (+ any group padding)
    assert bench.PERF["devices_used"] == S.host_device_count() == 2
    new_groups = bench.PERF["groups"][g0:]
    assert new_groups, "planned execution must record its groups"
    for g in new_groups:
        assert {"lanes", "capacity", "shards", "scout", "steps",
                "compile_s", "exec_s"} <= set(g)


def test_prefetch_serves_run_workload_from_cache(tiny_cfg):
    """A prefetched figure phase is served from the run cache, and the
    results are the planner's (bit-identical either way)."""
    from repro.ssd.sweep_plan import RunRequest, prefetch

    bench.clear_caches()
    try:
        req = RunRequest("hm_0", tiny_cfg, ("baseline", "venice"),
                         n_requests=30)
        prefetch([req])
        misses = bench.PERF["run_misses"]
        run = bench.run_workload("hm_0", tiny_cfg,
                                 designs=("baseline", "venice"),
                                 n_requests=30)
        assert bench.PERF["run_misses"] == misses  # cache hit, no re-plan
        assert set(run.results) == {"baseline", "venice"}
        prefetch([req])  # idempotent: nothing pending
        assert bench.PERF["run_misses"] == misses
    finally:
        bench.clear_caches()


def test_single_device_environment_parity(tiny_cfg, tiny_txns):
    """The planner must be bit-exact in a plain 1-device environment.

    The suite forces 2 host devices (conftest), so the 1-device check runs
    in a subprocess with the forcing stripped; digests of every parity
    field must match the in-process (sharded, decomposed) run."""
    sweep = simulate_sweep(tiny_cfg, tiny_txns, DESIGNS, seeds=5,
                           decompose=True)
    h = hashlib.sha1()
    for lane in sweep:
        for f in PARITY_FIELDS:
            h.update(np.ascontiguousarray(getattr(lane, f)).tobytes())
    expect = h.hexdigest()

    script = r"""
import hashlib
import numpy as np
import jax
from repro.ssd import DESIGNS, decompose_trace, perf_optimized, simulate_sweep
from repro.traces.generator import gen_trace, to_pages

assert len(jax.devices()) == 1, jax.devices()
cfg = perf_optimized(rows=2, cols=2, pages_per_block=64)
tr = gen_trace("src2_1", 60, seed=3)
tr = dict(tr)
tr["arrival_us"] = tr["arrival_us"] / 16.0
pages = to_pages(tr, cfg.page_bytes)
txns = decompose_trace(cfg, pages,
                       footprint_pages=int(pages["footprint_pages"]))
sweep = simulate_sweep(cfg, txns, DESIGNS, seeds=5, decompose=True)
h = hashlib.sha1()
for lane in sweep:
    for f in ("completion", "wait", "conflict", "hops", "tries",
              "misroutes"):
        h.update(np.ascontiguousarray(getattr(lane, f)).tobytes())
print("DIGEST", h.hexdigest())
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = " ".join(  # a stock environment: 1 device
        f for f in env.get("XLA_FLAGS", "").split()
        if "--xla_force_host_platform_device_count" not in f
    )
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-2000:]
    digest = [l for l in out.stdout.splitlines() if l.startswith("DIGEST")]
    assert digest and digest[0].split()[1] == expect


def _rand_txns(rng, n, n_planes):
    return {
        "arrival": rng.integers(0, 50_000, n),
        "kind": rng.integers(0, 3, n),
        "plane": rng.integers(0, n_planes, n),
        "nbytes": rng.choice([512, 4096, 16384], n).astype(np.int64),
    }


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_nominal_order_vectorized_matches_loop(tiny_cfg, seed):
    """The grouped-cumsum ``_nominal_order`` is pinned bit-exact to the
    per-transaction reference loop on adversarial random streams (plane
    collisions, equal arrivals, all three kinds)."""
    rng = np.random.default_rng(seed)
    txns = _rand_txns(rng, 4000, tiny_cfg.n_planes)
    assert np.array_equal(S._nominal_order(tiny_cfg, txns),
                          S._nominal_order_ref(tiny_cfg, txns))


def test_empty_trace_all_decompose_flags(tiny_cfg):
    """An empty transaction set must return empty results on every path
    (decompose=True used to assume at least one row lane exists)."""
    empty = {k: np.empty((0,), np.int64)
             for k in ("arrival", "kind", "plane", "node", "row", "nbytes",
                       "req")}
    for flag in (False, "auto", True):
        for r in simulate_sweep(tiny_cfg, empty, ("baseline", "venice"),
                                seeds=1, decompose=flag):
            assert len(r.completion) == 0
            assert r.exec_ticks == 0


def test_nominal_order_fixture_and_edge_cases(tiny_cfg, tiny_txns):
    assert np.array_equal(S._nominal_order(tiny_cfg, tiny_txns),
                          S._nominal_order_ref(tiny_cfg, tiny_txns))
    empty = {k: np.empty((0,), np.int64)
             for k in ("arrival", "kind", "plane", "nbytes")}
    assert len(S._nominal_order(tiny_cfg, empty)) == 0
    one = {"arrival": np.array([7]), "kind": np.array([0]),
           "plane": np.array([3]), "nbytes": np.array([4096])}
    assert np.array_equal(S._nominal_order(tiny_cfg, one),
                          S._nominal_order_ref(tiny_cfg, one))
