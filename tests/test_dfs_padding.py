"""The batched scout groups' DFS and retry counters and their readers: the
loop's iterations times its lanes never fall below the DFS steps of the
lanes' own walks, nor the tries walked below the tries taken; for one lane
in a group of its padding copies the retry loop runs ceil(tries / R)
rounds a scan step, and walked one try per call its iterations are
exactly its steps, every retried walk included, plus one per scan step
past its last transaction, once per copy; the readers find nothing
without a scout group."""
import numpy as np
import pytest

from chipbench import run as R
from repro.ssd import bench, decompose_trace
from repro.ssd import sim as S
from repro.ssd import sweep_plan as SP
from repro.kernels.scout_step import link_pad
from repro.ssd.config import cost_optimized
from repro.ssd.designs import sweep_layout_geom
from repro.traces import gen_trace
from repro.traces.generator import to_pages


def _ctx(**perf):
    return dict(perf=dict(sim_s=8.0, exec_s=5.0, **perf), host_s=10.0)


def test_reader():
    ctx = _ctx(dfs_steps_live=400, dfs_steps_padded=1000)
    assert R.read_metric("dfs_padded_per_live", ctx) == pytest.approx(2.5)


@pytest.mark.parametrize("perf", [
    dict(dfs_steps_live=0, dfs_steps_padded=0),  # no scout group ran
    dict(),  # a program without the counters
])
def test_nothing_to_read_without_a_scout_group(perf):
    assert R.read_metric("dfs_padded_per_live", _ctx(**perf)) is None


@pytest.fixture()
def occupancy(monkeypatch):
    monkeypatch.setattr(SP, "PLANNER_PROFILE", "occupancy")
    bench.clear_caches()
    SP._CAP_SEEN.clear()
    yield
    bench.clear_caches()
    SP._CAP_SEEN.clear()


def _txns(cfg, workload, n, seed):
    pages = to_pages(gen_trace(workload, n, seed), cfg.page_bytes)
    return decompose_trace(cfg, pages,
                           int(pages["offset_page"].max()) + 8)


def _sweep(cfg, txns, designs, seeds):
    """Results, this sweep's scout group records and counter deltas."""
    g0 = len(bench.PERF["groups"])
    keys = ("dfs_steps_live", "dfs_steps_padded", "scout_walk_calls",
            "scout_scan_steps", "scout_tries_walked")
    before = {k: bench.PERF[k] for k in keys}
    out = S.simulate_sweep(cfg, txns, designs, seeds=seeds, decompose=False)
    scout = [g for g in bench.PERF["groups"][g0:] if g["variant"] == "bscout"]
    return out, scout, {k: bench.PERF[k] - before[k] for k in keys}


def test_padded_covers_live_on_a_group(occupancy):
    """Venice and k-scout lanes in one group, on a 4x4 mesh of two-die
    packages: the loop's iterations times its lanes cover the DFS steps of
    every lane's own walks, and the group records add up in ``PERF``."""
    cfg = cost_optimized(rows=4, cols=4, dies_per_chip=2)
    txns = _txns(cfg, "proj_3", 60, 3)
    _, scout, delta = _sweep(cfg, txns, ("venice", "venice_kscout", "venice"),
                             seeds=(5, 6, 7))
    assert len(scout) == 1
    assert scout[0]["dfs_steps_live"] == delta["dfs_steps_live"] > 0
    assert scout[0]["dfs_steps_padded"] == delta["dfs_steps_padded"]
    assert delta["dfs_steps_padded"] >= delta["dfs_steps_live"]


def test_one_lane_group_walks_its_tries_in_rounds_of_R(occupancy,
                                                      monkeypatch):
    """One Venice lane, some of whose transactions retried their walk, in
    the planner's group of that lane and its 7 padding copies a shard: a
    call walks R = slots // 8 tries of each copy, so the retry loop ran
    ceil(tries / R) rounds per scan step, and one per scan step past the
    lane's last transaction, in each shard.  Walked one try a lane per
    call (R = 1), the loop ran once per live DFS step, retried walks
    included, plus once per tail step, on every copy; the live DFS steps
    are the same at both R."""
    cfg = cost_optimized(rows=4, cols=4, dies_per_chip=2)
    txns = _txns(cfg, "proj_3", 60, 3)
    shards = S.host_device_count()
    lanes = 8 * shards  # the planner's padded group width
    lay = sweep_layout_geom(cfg.rows, cfg.cols)
    R = S.try_slots(8, 1, link_pad(lay.L_pad), lay.n_nodes) // 8
    (res,), scout, delta = _sweep(cfg, txns, ("venice",), seeds=(9,))
    tries = np.asarray(res.tries)
    assert (tries > 1).any() and R > 1  # a retried walk
    n = len(res.completion)
    tail = -(-n // S.CHUNK) * S.CHUNK - n
    assert len(scout) == 1
    assert delta["scout_scan_steps"] == shards * (n + tail)
    assert delta["scout_walk_calls"] == shards * (
        int(np.sum(-(-tries // R))) + tail)

    make = S._make_batched_scout_step
    monkeypatch.setattr(S, "_make_batched_scout_step",
                        lambda *a, **k: make(*a, **k, slots=1))
    bench.clear_caches()
    S.clear_exec_cache()
    S._build_batched_scout_fn.cache_clear()
    try:
        (one,), _, at1 = _sweep(cfg, txns, ("venice",), seeds=(9,))
    finally:
        S.clear_exec_cache()
        S._build_batched_scout_fn.cache_clear()
    assert np.array_equal(np.asarray(one.tries), tries)
    assert at1["dfs_steps_live"] == delta["dfs_steps_live"]
    assert at1["dfs_steps_padded"] == lanes * (at1["dfs_steps_live"] + tail)
    assert at1["scout_walk_calls"] == shards * (int(tries.sum()) + tail)


def test_walk_calls_per_step_reader():
    ctx = _ctx(scout_walk_calls=2500, scout_scan_steps=1000)
    assert R.read_metric("walk_calls_per_step", ctx) == pytest.approx(2.5)


@pytest.mark.parametrize("perf", [
    dict(scout_walk_calls=0, scout_scan_steps=0),  # no scout group ran
    dict(dfs_steps_live=0, dfs_steps_padded=0),  # a program without them
])
def test_no_walk_calls_per_step_without_a_scout_group(perf):
    assert R.read_metric("walk_calls_per_step", _ctx(**perf)) is None


def test_tries_walked_cover_the_tries_taken(occupancy):
    """Venice and k-scout lanes in one group: the tries walked, speculative
    ones included, cover the tries of the lanes' valid transactions; every
    scan step of every shard ran at least one round; the group records add
    up in ``PERF``."""
    cfg = cost_optimized(rows=4, cols=4, dies_per_chip=2)
    txns = _txns(cfg, "proj_3", 60, 3)
    out, scout, delta = _sweep(cfg, txns, ("venice", "venice_kscout"),
                               seeds=(5, 6))
    assert len(scout) == 1
    taken = sum(int(np.asarray(r.tries).sum()) for r in out)
    assert delta["scout_tries_walked"] >= taken > len(out)
    assert delta["scout_walk_calls"] >= delta["scout_scan_steps"] > 0
    for k in ("scout_walk_calls", "scout_scan_steps", "scout_tries_walked"):
        assert scout[0][k] == delta[k]
