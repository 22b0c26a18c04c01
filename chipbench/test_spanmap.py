"""Program spans on the trace's clock: idle time named by spans, the
clock check, and the trace's own start time.  Hand-made intervals and the
trace recorded on a TPU v5e (``record_testdata.py``)."""
import os

import pytest

from chipbench import profile, spanmap

TESTDATA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "testdata")
RECORDED = os.path.join(TESTDATA, "sweep.xplane.pb.gz")
US = 1000.0


def _ops():
    # busy 20-30, 30-40 (touching), 40.004-50 (a 4 ns gap), 200-300
    return dict(devices={"/device:TPU:0": [
        ("%a.1 = s32[8] fusion()", 20 * US, 30 * US),
        ("%b.2 = s32[8] fusion()", 30 * US, 40 * US),
        ("%c.3 = s32[8] fusion()", 40 * US + 4, 50 * US),
        ("%d.4 = s32[8] fusion()", 200 * US, 300 * US)]})


def _spans():
    # (name, start_ns, end_ns, args): a group holding pack, put and a run
    # whose fetch follows; nothing covers 395-400 us
    return [("group:batched", 0.0, 395 * US, None),
            ("pack", 5 * US, 15 * US, None),
            ("put", 15 * US, 19 * US, None),
            ("run", 19 * US, 310 * US, None),
            ("fetch", 310 * US, 330 * US, None)]


@pytest.fixture(scope="module")
def recorded():
    return spanmap.load(RECORDED)


def test_load_reads_the_trace_start(recorded):
    assert recorded["profile_start_time"] == 1792194127398398311
    assert recorded["devices"] == profile.load(RECORDED)["devices"]


def test_device_clock_from_tracer_anchor():
    events = [("span", "plan", "pack", 10.0, 5.0, {"batch": 1}, 1),
              ("instant", "compile", "launched", 12.0, 0.0, None, 1)]
    got = spanmap.device_clock(events, t0_wall_ns=1_000_500,
                               start_ns=1_000_000)
    assert got == [("pack", 500 + 10e3, 500 + 15e3, {"batch": 1})]


def test_reduce_without_spans_is_profile_reduce(recorded):
    kern = profile.kernel_patterns()
    assert spanmap.reduce(recorded, 0.2, kern) == profile.reduce(
        recorded, 0.2, kern)


def test_reduce_of_the_recorded_trace_is_unchanged(recorded):
    """The numbers and names the reduction gave the recorded trace before
    program spans existed."""
    red = spanmap.reduce(recorded, 0.2, profile.kernel_patterns())
    assert red["busy_s"] == pytest.approx(0.060247139, rel=1e-12)
    assert red["kernel_s"] == {"static_step": pytest.approx(0.002083924,
                                                            rel=1e-12)}
    assert [n for n, _ in red["device_ops"][:3]] == [
        "%while.395", "%while.396", "%while.397"]
    assert red["device_ops"][0][1] == pytest.approx(0.048113182, rel=1e-12)
    assert dict(red["idle_gaps"]) == {
        profile.EDGES: pytest.approx(0.098741312, rel=1e-12),
        "after %copy.123": pytest.approx(0.041011404, rel=1e-12),
        profile.SHORT_GAP: pytest.approx(1.45e-7, rel=1e-9)}


def test_idle_named_after_the_innermost_span():
    trace = _ops()
    red = spanmap.reduce(trace, 400e-6, {"target": "x", "kernels": {}},
                         _spans())
    gaps = dict(red["idle_gaps"])
    # leading edge 0-20: pack 5-15, put 15-19, run 19-20, the group 0-5
    assert gaps["idle under pack"] == pytest.approx(10e-6)
    assert gaps["idle under put"] == pytest.approx(4e-6)
    # run: 19-20 at the leading edge, the 50-200 gap between ops and
    # 300-310 at the trailing edge
    assert gaps["idle under run"] == pytest.approx(161e-6)
    # trailing edge 300-400: fetch 310-330, the group to 395
    assert gaps["idle under fetch"] == pytest.approx(20e-6)
    assert gaps["idle under group:batched"] == pytest.approx(5e-6 + 65e-6)
    assert gaps[spanmap.OUTSIDE] == pytest.approx(5e-6)
    assert gaps[profile.SHORT_GAP] == pytest.approx(4e-9)
    assert profile.EDGES not in gaps
    # the same idle time as without spans, only named
    plain = profile.reduce(trace, 400e-6, {"target": "x", "kernels": {}})
    assert sum(gaps.values()) == pytest.approx(
        sum(v for _, v in plain["idle_gaps"]))
    assert red["idle_s"] == pytest.approx(400e-6 - red["busy_s"])
    assert red["unnamed_idle_s"] == pytest.approx(5e-6)
    for k in ("busy_s", "window_s", "kernel_s", "device_ops"):
        assert red[k] == plain[k]


def test_window_start_places_the_edges():
    """The same ops and edge total, with the window starting at 10 us:
    10 us lead before the first op, the rest after the last."""
    red = spanmap.reduce(_ops(), 400e-6, {"target": "x", "kernels": {}},
                         _spans(), window_start_ns=10 * US)
    gaps = dict(red["idle_gaps"])
    assert gaps["idle under pack"] == pytest.approx(5e-6)
    assert gaps["idle under group:batched"] == pytest.approx(65e-6)
    assert gaps[spanmap.OUTSIDE] == pytest.approx(15e-6)


def test_uncovered_idle_and_an_empty_device():
    trace = dict(devices={"/device:TPU:0": [], "/device:TPU:1": [
        ("%a.1 = s32[8] fusion()", 0.0, 100 * US)]})
    red = spanmap.reduce(trace, 200e-6, {"target": "x", "kernels": {}},
                         [("run", 0.0, 50 * US, None)])
    gaps = dict(red["idle_gaps"])
    # device 0 idle all 200 us (50 under run), device 1 idle 100-200
    assert gaps["idle under run"] == pytest.approx(25e-6)
    assert gaps[spanmap.OUTSIDE] == pytest.approx((150e-6 + 100e-6) / 2)


def test_clock_offsets():
    trace = _ops()
    runs = [("run", 19 * US, 60 * US, None), ("run", 150 * US, 299 * US,
                                              None)]
    got = spanmap.clock_offsets(trace, runs)
    assert got["n_ops"] == 4 and got["n_runs"] == 2
    # %d.4 ends 1 us after the second run span
    assert got["n_outside"] == 1
    assert got["max_outside_ns"] == pytest.approx(1 * US)
    assert got["lead_ns"] == [pytest.approx(1 * US), pytest.approx(50 * US)]
    assert got["tail_ns"] == [pytest.approx(10 * US), pytest.approx(-1 * US)]
    with pytest.raises(ValueError):
        spanmap.clock_offsets(trace, [])


def test_idle_trace_rehearses_on_the_cpu(tmp_path, monkeypatch):
    """The probe end to end at a tiny size: sweeps before, under and after
    the trace, their page faults, the per-layer metrics.  The CPU's trace
    holds no device ops, so nothing is named."""
    from chipbench import idle_trace
    from chipbench import run as R

    monkeypatch.setattr(R, "TRACE_DIR", str(tmp_path / "trace"))
    cell = idle_trace.shorten(R.load_cell("perf.static-long"), 40)
    out = idle_trace.trace_cell(cell, 2**31 + 3, 5, 3, require_tpu=False,
                                log=lambda msg: None)
    assert [s["traced"] for s in out["sweeps"]] == [False] * 3 + [True,
                                                                 False]
    assert all(s["minor_faults"] >= 0 for s in out["sweeps"])
    pace = out["traced_sweep"]
    assert pace["whole"] and pace["traced_s"] > 0 and pace["untraced_s"] > 0
    for stage in ("trace", "ftl", "lower", "pack", "put", "run", "fetch",
                  "unpack"):
        assert out["spans_s"][stage] > 0, stage
    assert out["sweeps"][3]["stages_s"]["pack_s"] == pytest.approx(
        out["spans_s"]["pack"])
    for name in ("lower_share", "pack_share", "put_share", "fetch_share",
                 "unpack_share", "put_bytes_per_valid", "host_pack_share"):
        assert out["metrics"][name] > 0, name
    assert out["idle_gaps"] == [] and out["device"]["busy_s"] == 0.0
