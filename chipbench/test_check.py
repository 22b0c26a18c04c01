"""The comparison that decides ``correct``, driven through the rest of a run
on the CPU at a small size: a sound run passes, the control fails, and so
does a run whose timed path is broken underneath, once per fault the cells
can have.  (The cells' lanes are independent simulations: no exchange
between chips exists to leave out.)"""
import types

import numpy as np
import pytest

from chipbench import check
from chipbench import run as R


def _small_cell():
    cell = R.load_cell("perf.fig9-msr")
    conf = dict(cell["config"])
    conf["ssd"] = dict(conf["ssd"], rows=2, cols=2, pages_per_block=64)
    cell["config"] = conf
    cell["traffic"] = dict(
        cell["traffic"], workloads=["hm_0", "src2_1"],
        designs=["baseline", "pnssd", "venice"], trace_seeds_per_sweep=1,
        sweeps=2, requests_per_trace=60,
        warmup=[{"designs": ["baseline", "pnssd", "venice"], "requests": 60}],
        # every design run of the window is checked
        check=[{"designs": ["venice"], "count": 4},
               {"designs": ["baseline", "pnssd"], "count": 8}])
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


def _run(seed=11):
    return R.run_cell(_small_cell(), seed, 30.0, False, require_tpu=False,
                      log=lambda msg: None)


def test_sound_run_is_correct(fresh):
    line = _run()
    assert line["correct"], line["checks"]
    assert line["attempted"] == 2 * 2 * 3
    assert list(line)[-1] == "checks"
    assert line["checks"]["txn_mismatch"] == {"value": 0, "limit": 0}


def test_traced_run_reads_per_layer_metrics(fresh, monkeypatch):
    """The traced run's wiring, with a made-up trace in the profiler's place
    (a CPU trace has no device plane): the traced sweep is the second, and
    the line carries the busy time, the breakdown and the counter metrics."""
    from chipbench import profile, roofline

    us = 1000.0
    op = '%closed_call.1 = s32[8] custom-call() custom_call_target="tpu_custom_call"'
    fake = dict(devices={"/device:TPU:0": [(op, 10 * us, 40 * us)]})
    monkeypatch.setattr(profile, "load", lambda path: fake)
    monkeypatch.setattr(roofline, "peak", lambda kind: {"hbm_bytes_per_s": 1e9})
    monkeypatch.setattr(R, "BoundedTrace", lambda path, seconds:
                        types.SimpleNamespace(close=lambda: None, t0=0.0,
                                              t1=100e-6, whole=True))
    logs = []
    line = R.run_cell(_small_cell(), 11, 30.0, True, require_tpu=False,
                      log=logs.append)
    assert line["correct"], line["checks"]
    assert any(m.startswith("[sweep 1]") and "(traced)" in m for m in logs)
    assert line["device"]["busy_s"] == pytest.approx(30e-6)
    assert line["device"]["window_s"] == pytest.approx(100e-6)
    assert {"ftl_share", "padded_per_valid", "host_pack_share"} <= set(
        line["metrics"])
    assert line["breakdown"]["device_ops"][0][0] == "%closed_call.1"
    assert line["traced_sweep"]["whole"]
    assert list(line)[-1] == "checks"


def test_control_fails(fresh):
    """The reference at half the time resolution, in the program's place."""
    cell = _small_cell()
    sweeper = R.Sweeper(cell, run_tag="control")
    pool = R.plan_sweeps(cell, 5)
    results = [sweeper.run(sw) for sw in pool]
    sound = check.check_sample(cell, 5, sweeper, results, pool,
                               log=lambda m: None)
    ctrl = check.check_sample(cell, 5, sweeper, results, pool, control=True,
                              log=lambda m: None)
    assert sound["correct"]
    assert not ctrl["correct"]
    assert ctrl["checks"]["txn_mismatch"]["value"] > 0


def _state_unchanged(make):
    def patched(*a, **k):
        step = make(*a, **k)

        def frozen(sp, state, xs):
            _, out = step(sp, state, xs)
            return state, out

        return frozen

    return patched


def _answer_altered(run_compiled):
    def patched(key, args, specs, **kw):
        outs, perf = run_compiled(key, args, specs, **kw)
        completion = np.array(outs.completion)  # time-major [cap, B]
        completion[0, :] += 1  # each lane's first answer
        return outs._replace(completion=completion), perf

    return patched


def _half_batch_left_out(dispatch, step_out):
    def patched(plan):
        perf = dispatch(plan)
        lanes = list({id(ln): ln for ln in plan.lanes}.values())
        for ln in lanes[len(lanes) // 2:]:
            ln.out = step_out(*(np.zeros_like(a) for a in ln.out))
        return perf

    return patched


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch_left_out",
                                   "answer_altered"])
def test_broken_timed_path_is_not_correct(fresh, monkeypatch, fault):
    from repro.ssd import sweep_plan

    sim = fresh
    if fault == "state_unchanged":
        monkeypatch.setattr(sim, "_make_batched_static_step",
                            _state_unchanged(sim._make_batched_static_step))
        monkeypatch.setattr(sim, "_make_batched_scout_step",
                            _state_unchanged(sim._make_batched_scout_step))
    elif fault == "half_batch_left_out":
        monkeypatch.setattr(sweep_plan, "_dispatch", _half_batch_left_out(
            sweep_plan._dispatch, sim.StepOut))
    else:
        monkeypatch.setattr(sim, "_run_compiled",
                            _answer_altered(sim._run_compiled))
    line = _run()
    assert not line["correct"]
    assert line["failed"] > 0
    assert line["checks"]["txn_mismatch"]["value"] > 0
