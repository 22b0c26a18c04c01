"""The comparison that decides ``correct`` on a mesh past one 128-lane row
of links, with two dies per chip: ``cost-16ch.fig9-wide`` cut to a 16x5
mesh (139 links), its single-die chips doubled (160 dies, so the FTL's die
striping runs) and 60 requests per trace, driven through the
benchmark's run on the CPU with the accelerator's planner layout.  A sound
run equals the plain reference (``chipbench/reference``, which reads rows,
columns and dies from the configuration), and a run whose lanes keep their
state unchanged or hand back an altered answer does not."""
import numpy as np
import pytest

from chipbench import run as R


def _wide_cell():
    cell = R.load_cell("cost-16ch.fig9-wide")
    conf = dict(cell["config"])
    conf["ssd"] = dict(conf["ssd"], rows=16, cols=5, dies_per_chip=2,
                       pages_per_block=64)
    cell["config"] = conf
    cell["traffic"] = dict(
        cell["traffic"], workloads=["hm_0", "proj_3"],
        designs=["baseline", "pnssd", "venice"], trace_seeds_per_sweep=1,
        sweeps=1, requests_per_trace=60,
        warmup=[{"designs": ["baseline", "pnssd", "venice"], "requests": 60}],
        # every design run of the window is checked
        check=[{"designs": ["venice"], "count": 2},
               {"designs": ["baseline", "pnssd"], "count": 4}])
    return cell


@pytest.fixture()
def fresh(monkeypatch):
    """The accelerator's planner layout (batched runners), with every
    cache of compiled programs and results dropped before and after."""
    from repro.ssd import bench, sim, sweep_plan

    def clear():
        bench.clear_caches()
        sim.clear_exec_cache()
        sim._build_batched_fn.cache_clear()
        sim._build_batched_scout_fn.cache_clear()
        sweep_plan._CAP_SEEN.clear()

    monkeypatch.setattr(sweep_plan, "PLANNER_PROFILE", "occupancy")
    clear()
    yield sim
    clear()


def _frozen(make):
    def patched(*a, **k):
        step = make(*a, **k)

        def frozen(sp, state, xs):
            _, out = step(sp, state, xs)
            return state, out

        return frozen

    return patched


def _altered(run_compiled):
    def patched(key, args, specs, **kw):
        outs, perf = run_compiled(key, args, specs, **kw)
        completion = np.array(outs.completion)  # time-major [cap, B]
        completion[0, :] += 1  # each lane's first answer
        return outs._replace(completion=completion), perf

    return patched


@pytest.mark.parametrize("fault", [None, "state_unchanged", "answer_altered"])
def test_wide_mesh_two_dies_against_the_reference(fresh, monkeypatch, fault):
    sim = fresh
    if fault == "state_unchanged":
        monkeypatch.setattr(sim, "_make_batched_static_step",
                            _frozen(sim._make_batched_static_step))
        monkeypatch.setattr(sim, "_make_batched_scout_step",
                            _frozen(sim._make_batched_scout_step))
    elif fault == "answer_altered":
        monkeypatch.setattr(sim, "_run_compiled",
                            _altered(sim._run_compiled))
    line = R.run_cell(_wide_cell(), 2**31 + 12345, 30.0, False,
                      require_tpu=False, log=lambda msg: None)
    assert line["attempted"] == 2 * 3
    if fault is None:
        assert line["correct"], line["checks"]
        assert line["checks"]["txn_mismatch"] == {"value": 0, "limit": 0}
        assert line["checks"]["req_mismatch"] == {"value": 0, "limit": 0}
    else:
        assert not line["correct"]
        assert line["failed"] > 0
        assert line["checks"]["txn_mismatch"]["value"] > 0
