"""The batched scout groups' DFS counters and their reader: the loop's
iterations times its lanes never fall below the DFS steps of the lanes'
own walks; for one lane in a group of its padding copies they are exactly
its steps, every retried walk included, plus one per scan step past its
last transaction, once per copy; the reader finds nothing without a scout
group."""
import numpy as np
import pytest

from chipbench import run as R
from repro.ssd import bench, decompose_trace
from repro.ssd import sim as S
from repro.ssd import sweep_plan as SP
from repro.ssd.config import cost_optimized
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
    keys = ("dfs_steps_live", "dfs_steps_padded")
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


def test_one_lane_group_pads_only_by_its_copies_and_its_tail(occupancy):
    """One Venice lane, some of whose transactions retried their walk, in
    the planner's group of that lane and its padding copies: each copy
    walks the same DFS, so the loop ran once per live DFS step, retried
    walks included, plus once for each scan step past the lane's last
    transaction."""
    cfg = cost_optimized(rows=4, cols=4, dies_per_chip=2)
    txns = _txns(cfg, "proj_3", 60, 3)
    (res,), scout, delta = _sweep(cfg, txns, ("venice",), seeds=(9,))
    assert (np.asarray(res.tries) > 1).any()  # a retried walk
    n = len(res.completion)
    tail = -(-n // S.CHUNK) * S.CHUNK - n
    lanes = 8 * S.host_device_count()  # the planner's padded group width
    assert len(scout) == 1
    assert delta["dfs_steps_padded"] == lanes * (delta["dfs_steps_live"]
                                                 + tail)
