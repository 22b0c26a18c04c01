"""The trace reduction: on hand-made intervals, and on a small trace
recorded on a TPU v5e (``record_testdata.py``)."""
import json
import os

import pytest

from chipbench import profile, roofline

TESTDATA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "testdata")
TARGET = 'custom_call_target="tpu_custom_call"'
KERNELS = {"target": TARGET,
           "kernels": {"static_step": ["%kern_a"], "scout_step": ["%kern_b"]}}


def _trace():
    # device 0 busy 10-30 and 25-40 (overlap) and 90-110; device 1 busy
    # 0-50 and 55-60, with a zero-length op at 50
    us = 1000.0
    return dict(devices={
        "/device:TPU:0": [(f"%kern_a.1 = s32[8] custom-call() {TARGET}",
                           10 * us, 30 * us),
                          ("%fusion.2 = s32[8] fusion()", 25 * us, 40 * us),
                          (f"%kern_b.3 = s32[8] custom-call() {TARGET}",
                           90 * us, 110 * us)],
        "/device:TPU:1": [(f"%kern_a.1 = s32[8] custom-call() {TARGET}",
                           0 * us, 50 * us),
                          ("%kern_a_copy = s32[8] copy()", 50 * us, 50 * us),
                          ("%fusion.4 = s32[8] fusion()", 55 * us, 60 * us)],
    })


def test_union():
    assert profile.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]


def test_reduce_hand_counted():
    red = profile.reduce(_trace(), 120e-6, KERNELS)
    # device 0: 10-40 and 90-110 = 50 us; device 1: 0-50 and 55-60 = 55 us
    assert red["busy_s"] == pytest.approx(52.5e-6)
    assert red["window_s"] == pytest.approx(120e-6)
    assert red["n_devices"] == 2
    # kern_a: 20 us on device 0 + 50 us on device 1; kern_b 20 us; the copy
    # is no Mosaic call
    assert red["kernel_s"]["static_step"] == pytest.approx(70e-6)
    assert red["kernel_s"]["scout_step"] == pytest.approx(20e-6)
    ops = dict(red["device_ops"])
    assert ops["%kern_a.1"] == pytest.approx(35e-6)  # per device
    gaps = dict(red["idle_gaps"])
    # per device: device 0 idle 40-90 after the fusion, 20 us at the edges
    # of its 10-110 span; device 1 idle 50-55 (short), 60 us at the edges
    assert gaps["after %fusion.2"] == pytest.approx(25e-6)
    assert gaps[profile.SHORT_GAP] == pytest.approx(2.5e-6)
    assert gaps[profile.EDGES] == pytest.approx(40e-6)
    idle = 120e-6 - red["busy_s"]
    assert sum(gaps.values()) == pytest.approx(idle)


def test_reduce_of_a_trace_cut_inside_the_sweep():
    """Stopped by its timer mid-sweep: the window is the traced part, and
    the tracer says the trace is not the whole sweep."""
    import time

    from chipbench import run as R

    red = profile.reduce(_trace(), 110e-6, KERNELS)
    assert red["window_s"] == pytest.approx(110e-6)
    assert dict(red["idle_gaps"])[profile.EDGES] == pytest.approx(30e-6)
    with pytest.raises(ValueError):
        profile.reduce(dict(devices={}), 1.0, KERNELS)
    cut = R.BoundedTrace(R.TRACE_DIR, 0.05)
    time.sleep(0.5)
    cut.close()
    assert not cut.whole and cut.t1 - cut.t0 < 0.4
    whole = R.BoundedTrace(R.TRACE_DIR, 60.0)
    whole.close()
    assert whole.whole


RECORDED = os.path.join(TESTDATA, "sweep.xplane.pb.gz")


def _extent(trace):
    """Seconds from the first device op to the last."""
    ops = [o for v in trace["devices"].values() for o in v]
    return (max(e for _, _, e in ops) - min(s for _, s, _ in ops)) * 1e-9


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(TESTDATA, "sweep_groups.json")) as f:
        groups = json.load(f)
    return profile.load(RECORDED), groups


def test_recorded_trace_reduces(recorded):
    trace, groups = recorded
    assert groups["device_kind"] == "TPU v5 lite"
    red = profile.reduce(trace, _extent(trace), profile.kernel_patterns())
    assert red["n_devices"] == 1
    assert 0 < red["busy_s"] <= red["window_s"]
    # the kernel is found under the names kernel_names.json lists
    assert red["kernel_s"]["static_step"] > 0
    assert len(red["device_ops"]) == profile.TOP
    assert red["idle_gaps"]


def test_recorded_rooflines_stay_under_100(recorded):
    trace, groups = recorded
    red = profile.reduce(trace, _extent(trace), profile.kernel_patterns())
    with open(os.path.join(os.path.dirname(TESTDATA), "configs",
                           "perf.json")) as f:
        conf = json.load(f)
    least = roofline.least_bytes(groups["groups"], conf)
    peak = roofline.peak(groups["device_kind"])["hbm_bytes_per_s"]
    s = roofline.share(least["static_step"], red["kernel_s"]["static_step"],
                       peak)
    assert 0 < s < 100
