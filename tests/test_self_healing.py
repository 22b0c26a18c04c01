"""Self-healing compile backend: a dead/hung compile server never hangs
or fails a run (ISSUE 8).

The compile server (``sweep_plan._schedule_compiles`` -> ``xc_worker``)
is a scheduling hint with no correctness surface; these tests pin the
recovery paths that keep it that way:

* a SIGKILLed worker is detected by ``_await_server`` (nonzero
  returncode -> "crashed"), every delegated key falls back to the
  in-process compile, and the watchdog counters say so;
* an alive-but-silent worker (stale heartbeat) trips the
  ``_ServerWatchdog`` within its timeout — never the 600s poll deadline —
  and is killed and abandoned;
* end-to-end: SIGKILLing the worker right after it is spawned leaves a
  streamed run bit-identical to the clean rerun.
"""
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from repro.ssd import bench, exec_cache
from repro.ssd import sim as S
from repro.ssd import sweep_plan as SP
from repro.ssd.stream import stream_simulate
from repro.traces.generator import gen_trace

PARITY_FIELDS = ("completion", "wait", "conflict", "hops", "tries",
                 "misroutes", "failed")


@pytest.fixture()
def server_state():
    """Run against a clean compile-server slate; never leak a fake/killed
    server (or its delegated keys) into other tests."""
    assert SP._PROC is None and not SP._PROC_KEYS

    def reset():
        if SP._PROC is not None and SP._PROC.poll() is None:
            SP._PROC.kill()
            SP._PROC.wait()
        SP._PROC = None
        SP._PROC_KEYS.clear()
        SP._WATCHDOG = None

    reset()
    yield
    reset()


def _fake_worker() -> subprocess.Popen:
    return subprocess.Popen(
        [sys.executable, "-c", "import time; time.sleep(600)"])


def test_sigkilled_worker_falls_back_fast(tmp_path, monkeypatch,
                                          server_state):
    """SIGKILL -> ``_await_server`` sees the nonzero returncode at once,
    records the crash, and compiles in-process."""
    hb = str(tmp_path / "wk.hb")
    open(hb, "w").close()
    proc = _fake_worker()
    key = ("lane", "sigkill-test")
    SP._PROC = proc
    SP._PROC_KEYS.add(key)
    SP._WATCHDOG = SP._ServerWatchdog(hb, timeout_s=30.0)
    compiled = []
    monkeypatch.setattr(
        S, "ensure_compiled",
        lambda k, *a, **kw: compiled.append(k) or "sentinel")
    proc.kill()
    proc.wait()
    trips0 = bench.PERF["xc_watchdog_trips"]
    fb0 = bench.PERF["xc_watchdog_fallbacks"]
    t0 = time.perf_counter()
    out = SP._await_server(key)
    assert time.perf_counter() - t0 < 30.0  # immediate, not the deadline
    assert out == "sentinel" and compiled == [key]
    assert bench.PERF["xc_watchdog_trips"] == trips0 + 1
    assert bench.PERF["xc_watchdog_reason"] == "crashed"
    assert bench.PERF["xc_watchdog_fallbacks"] == fb0 + 1
    assert SP._PROC is None and not SP._PROC_KEYS


def test_stale_heartbeat_trips_watchdog(tmp_path, monkeypatch,
                                        server_state):
    """A worker that is alive but silent (SIGSTOP/swap-death analogue:
    the heartbeat file stops changing) is abandoned at the heartbeat
    deadline and killed; the key compiles in-process."""
    hb = str(tmp_path / "wk.hb")
    open(hb, "w").close()
    proc = _fake_worker()  # alive, but never touches the heartbeat file
    key = ("lane", "hang-test")
    SP._PROC = proc
    SP._PROC_KEYS.add(key)
    SP._WATCHDOG = SP._ServerWatchdog(hb, timeout_s=0.3)
    monkeypatch.setattr(S, "ensure_compiled",
                        lambda k, *a, **kw: "sentinel")
    trips0 = bench.PERF["xc_watchdog_trips"]
    fb0 = bench.PERF["xc_watchdog_fallbacks"]
    t0 = time.perf_counter()
    out = SP._await_server(key)
    assert time.perf_counter() - t0 < 10.0
    assert out == "sentinel"
    assert bench.PERF["xc_watchdog_trips"] == trips0 + 1
    assert bench.PERF["xc_watchdog_reason"] == "heartbeat"
    assert bench.PERF["xc_watchdog_fallbacks"] == fb0 + 1
    assert SP._PROC is None and not SP._PROC_KEYS
    proc.wait(timeout=10)  # _fail_server killed the zombie
    assert proc.returncode is not None


def test_straggler_rule_flags_wedged_key():
    """The watchdog's straggler path: heartbeats keep coming but one
    key's wait dwarfs the median past the deadline floor — flagged after
    ``patience`` observations (driven with an injected clock, no 5s
    real-time waits)."""
    now = [0.0]
    wd = SP._ServerWatchdog.__new__(SP._ServerWatchdog)
    from repro.runtime.fault_tolerance import (HeartbeatMonitor,
                                               StragglerDetector)

    wd.hb_path = os.devnull  # mtime never changes; timeout is huge
    wd._clock = lambda: now[0]
    wd.mon = HeartbeatMonitor(["xc_worker"], timeout_s=1e9,
                              clock=wd._clock)
    wd.strag = StragglerDetector(k=4.0, deadline_floor_s=0.0, patience=3)
    wd.waits = {}
    wd._mtime = None
    wd._next_observe = now[0] + wd.OBSERVE_PERIOD_S
    wd.reason = None
    # one wedged key among three progressing ones: re-anchor the healthy
    # keys' wait start each round so only the wedged key accumulates
    t_start = time.perf_counter()
    wd.waits["wedged"] = t_start - 100.0
    for i in range(3):
        for k in ("a", "b", "c"):
            wd.waits[k] = time.perf_counter()
        now[0] += wd.OBSERVE_PERIOD_S
        healthy = wd.healthy()
        assert healthy == (i < 2), i
    assert wd.reason == "straggler"
    assert not wd.healthy()  # sticky


def test_run_completes_after_worker_sigkill(tiny_cfg, tmp_path,
                                            monkeypatch, server_state,
                                            no_jax_cache):
    """End-to-end acceptance: kill the real compile server the moment it
    is spawned mid-preset; the streamed run must complete and be
    bit-identical to the clean rerun."""
    monkeypatch.setenv("REPRO_XC_DIR", str(tmp_path / "xc"))
    monkeypatch.setenv("REPRO_COMPILE_PROC", "1")
    exec_cache.flush()
    S.clear_exec_cache()
    trace = gen_trace("prxy_0", 200, seed=3, footprint_bytes=1 << 20)
    span_s = float(trace["arrival_us"][-1]) * 1e-6
    designs = ("baseline", "venice", "venice_kscout")  # >= 3 lanec keys
    orig = SP._schedule_compiles
    killed = []

    def schedule_then_kill(keys):
        orig(keys)
        if SP._PROC is not None and SP._PROC.poll() is None:
            SP._PROC.kill()
            SP._PROC.wait()
            killed.append(True)

    monkeypatch.setattr(SP, "_schedule_compiles", schedule_then_kill)
    sr = stream_simulate(tiny_cfg, trace, designs, seeds=5,
                         window_s=max(2 * span_s, 1.0))
    assert killed, "the compile server was never spawned (keys < 3?)"
    monkeypatch.setattr(SP, "_schedule_compiles", orig)
    clean = stream_simulate(tiny_cfg, trace, designs, seeds=5,
                            window_s=max(2 * span_s, 1.0))
    for i, d in enumerate(designs):
        for f in PARITY_FIELDS:
            assert np.array_equal(getattr(sr.results[i], f),
                                  getattr(clean.results[i], f)), (d, f)
