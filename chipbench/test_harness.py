"""The harness's own arithmetic, called directly: no chip, no topology."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import roofline
from chipbench import run as R


def test_window_rule():
    assert R.should_start(0, 4, 0.0, 1.0, [])  # the first always runs
    assert R.should_start(1, 4, 10.0, 51.0, [10.0])
    assert R.should_start(2, 4, 30.0, 51.0, [10.0, 20.0])  # 21 left >= 15
    assert not R.should_start(2, 4, 40.0, 51.0, [20.0, 20.0])  # 11 < 20
    assert not R.should_start(4, 4, 1.0, 51.0, [0.1] * 4)  # pool is spent


def test_txn_rate():
    assert R.txn_rate([410_000, 412_000], 20.0) == pytest.approx(41_100.0)


def test_perf_delta_and_sum():
    before = dict(ftl_s=1.0, sim_s=5.0, exec_s=4.0, lanes=10,
                  groups=[{"variant": "batched"}], phase=None,
                  xc_watchdog_reason=None)
    after = dict(ftl_s=1.5, sim_s=9.0, exec_s=6.5, lanes=82,
                 groups=[{"variant": "batched"}, {"variant": "bscout"}],
                 phase=None, xc_watchdog_reason=None)
    d = R.perf_delta(before, after)
    assert d["ftl_s"] == pytest.approx(0.5)
    assert d["lanes"] == 72
    assert d["groups"] == [{"variant": "bscout"}]
    assert "phase" not in d
    s = R.sum_deltas([d, d])
    assert s["lanes"] == 144 and len(s["groups"]) == 2


def test_per_layer_readers():
    ctx = dict(
        perf=dict(ftl_s=1.0, sim_s=8.0, exec_s=6.0, scan_steps_valid=1000,
                  scan_steps_padded=1500),
        host_s=10.0,
        trace=dict(busy_s=3.0, window_s=4.0, kernel_s={"static_step": 2.0}),
        whole=True,
        least_bytes={"static_step": 819e9 * 0.5},
        peaks={"hbm_bytes_per_s": 819e9},
    )
    assert R.read_metric("ftl_share", ctx) == pytest.approx(10.0)
    assert R.read_metric("host_pack_share", ctx) == pytest.approx(20.0)
    assert R.read_metric("padded_per_valid", ctx) == pytest.approx(1.5)
    assert R.read_metric("static_step_roofline", ctx) == pytest.approx(25.0)
    # a trace cut inside the sweep: its window is not the one the least
    # bytes count
    ctx["whole"] = False
    assert R.read_metric("static_step_roofline", ctx) is None
    # no static kernel in the traced sweep: nothing to read, never a 0
    ctx["whole"], ctx["least_bytes"] = True, {}
    assert R.read_metric("static_step_roofline", ctx) is None


def test_least_bytes_hand_counted():
    ssd = dict(rows=8, cols=8, dies_per_chip=1, planes_per_die=2)
    # 8x8 mesh: 112 links, 8 controllers, 64 chip interfaces -> R_pad 184
    # static lane: 128 planes + 3 * 184 resources = 680 words
    assert roofline.state_words(ssd) == 680
    groups = [dict(variant="batched", steps=64 * 7 * 1024, lanes=60),
              dict(variant="bscout", steps=16 * 7 * 1024, lanes=12),
              dict(variant="lane", steps=1024, lanes=1)]
    got = roofline.least_bytes(groups, dict(ssd=ssd))
    assert got == {"static_step": 4 * (64 * 7 * 1024 * 18 + 2 * 60 * 680)}
    assert roofline.least_bytes(groups[1:], dict(ssd=ssd)) == {}


def test_peaks_table():
    assert roofline.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peak("TPU v99")


def test_result_line_keys():
    line = R.result_line(correct=True, attempted=72, failed=0,
                         metrics={"setup_s": {"value": 1.0, "unit": "s"}},
                         device={"platform": "tpu"},
                         checks={"txn_mismatch": {"value": 0, "limit": 0}},
                         breakdown={"device_ops": [], "idle_gaps": []},
                         traced_sweep={"traced_s": 2.1, "untraced_s": 2.0})
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "breakdown", "traced_sweep", "checks"]
    assert json.loads(json.dumps(line)) == line


with open(os.path.join(R.ROOT, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_every_seed_does_the_same_work(name):
    cell = R.load_cell(name)
    a, b = R.plan_sweeps(cell, 1), R.plan_sweeps(cell, 2**31 + 5)
    assert sorted(a) == sorted(b)  # the same sweeps, in the seed's order
    assert len({ts for sw in a for _, ts, _ in sw}) == len(a) * \
        cell["traffic"]["trace_seeds_per_sweep"]
    assert R.plan_sweeps(cell, 1) == a
    warm = {(w, ts) for w, ts, _ in R.warmup_sweep(cell)}
    assert not warm & {(w, ts) for sw in a for w, ts, _ in sw}


def test_benchmark_names_its_files():
    with open(os.path.join(R.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(R.ROOT, c["file"]))
    for w in bench["workloads"]:
        assert os.path.isfile(os.path.join(R.BENCH, "traffic",
                                           w["traffic"] + ".json"))
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(R.BENCH, "metrics",
                                           m["name"] + ".py"))


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "perf.fig9-msr",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cli_exits_nonzero_without_tpu():
    p = _cli(R.ROOT)
    assert p.returncode == 3, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_cli_exits_nonzero_without_the_program(tmp_path):
    shutil.copy(os.path.join(R.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(R.BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(str(tmp_path), {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
