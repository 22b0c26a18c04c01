"""Observability: flight-recorder reconstruction, trace schema, registry.

The load-bearing invariants:

* **Zero overhead off** — recording changes nothing: SimResult arrays are
  bit-identical with the recorder armed or disarmed (the scan carries no
  new state; reconstruction is post-hoc numpy).
* **Golden trace schema** — an exported trace is valid Chrome-trace-event
  JSON: nondecreasing timestamps, every B matched by an E in LIFO order,
  one ``cat="txn"`` X slice per recorded transaction.
* **Streamed == monolithic** — the flight-recorder run accumulated across
  stream windows (absolute-tick rebased) is array-identical to the run
  recorded from the monolithic planner pass of the same trace.
* **Per-run PERF deltas** — scenario engines publish the counter delta of
  their own run (``last_run_perf``), so back-to-back runs report
  independent (not cumulative) work.
"""
import json
import os
import warnings

import numpy as np
import pytest

from repro import obs
from repro.obs import events as obs_events
from repro.obs import spans as obs_spans
from repro.obs.export import SSD_PID0, HARNESS_PID, TraceBuilder, validate_trace
from repro.obs.heatmap import bucket_matrix, run_heatmaps
from repro.obs.registry import MetricsRegistry
from repro.ssd import bench, decompose_trace
from repro.ssd.sweep_plan import execute_sim_runs
from repro.traces.generator import gen_trace, to_pages

FIXTURE = os.path.join(os.path.dirname(__file__), "data", "msr_sample.csv")


@pytest.fixture(autouse=True)
def _clean_slate():
    """Every test starts untraced with cold run caches and leaves no
    tracer behind for the rest of the tier."""
    obs.disable_tracing()
    bench.clear_caches()
    yield
    obs.disable_tracing()
    bench.clear_caches()


def _run(cfg, txns, designs, seed=7):
    return execute_sim_runs(
        [(cfg, txns, tuple(designs), (seed,) * len(designs), "auto")]
    )[0]


# ---------------------------------------------------------------------------
# layer 2: metrics registry semantics
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_view_reset_snapshot_delta(self):
        reg = MetricsRegistry()
        reg.counter("hits")
        reg.timer("t_s")
        reg.object("groups", [])
        view = reg.view()
        view["hits"] += 2
        view["t_s"] += 0.5
        view["groups"].append("g0")
        snap = view.snapshot()
        view["hits"] += 3
        assert view.delta(snap) == {"hits": 3, "t_s": 0.0}
        alias = view  # reset is in place: aliases keep observing the view
        view.reset()
        assert alias is view and alias["hits"] == 0 and alias["t_s"] == 0.0
        assert alias["groups"] == []

    def test_redeclare_kind_conflict(self):
        reg = MetricsRegistry()
        reg.counter("x")
        reg.counter("x")  # same kind: idempotent
        with pytest.raises(ValueError):
            reg.timer("x")

    def test_bench_perf_is_registry_backed(self):
        # the historical keys survive the registry conversion — the
        # BENCH_*.json schema reads these directly
        for key in ("ftl_s", "sim_s", "compile_s", "exec_s", "groups",
                    "stream_windows", "kernel_backends",
                    "phase", "accel", "lower_s", "pack_s", "put_s",
                    "fetch_s", "unpack_s", "put_bytes"):
            assert key in bench.PERF, key
        snap = bench.PERF.snapshot()
        bench.PERF["decomp_hits"] += 1
        assert bench.PERF.delta(snap)["decomp_hits"] == 1
        bench.PERF["decomp_hits"] -= 1


# ---------------------------------------------------------------------------
# layer 1: flight recorder
# ---------------------------------------------------------------------------


class TestFlightRecorder:
    def test_fail_timeout_mirrors_sim(self):
        from repro.ssd import sim as S

        assert obs_events.FAIL_TIMEOUT == int(S.FAIL_TIMEOUT)

    def test_zero_overhead_off_bit_identity(self, tiny_cfg, tiny_txns):
        """Arming the recorder must not change a single output bit."""
        designs = ("baseline", "venice")
        off = _run(tiny_cfg, tiny_txns, designs)
        bench.clear_caches()
        obs.enable_tracing()
        on = _run(tiny_cfg, tiny_txns, designs)
        rec = obs_events.RECORDER
        assert rec is not None and len(rec.finalized_runs()) == len(designs)
        for a, b in zip(off, on):
            assert np.array_equal(a.completion, b.completion)
            assert np.array_equal(a.latency, b.latency)
            assert np.array_equal(a.wait, b.wait)
            assert np.array_equal(a.conflict, b.conflict)
            assert np.array_equal(a.hops, b.hops)
            assert a.exec_ticks == b.exec_ticks
            assert a.flash_energy_j == b.flash_energy_j

    def test_reconstruction_identity_static(self, tiny_cfg, tiny_txns):
        """completion == t0 + fc_stall + wait + d0 + op + d1, exactly."""
        obs.enable_tracing()
        _run(tiny_cfg, tiny_txns, ("baseline",))
        (run,) = obs_events.RECORDER.finalized_runs()
        tl = obs_events.derive_timeline(run)
        ph = tl["phases"]
        recon = (tl["t0"] + ph["fc_stall"] + ph["wait"] + ph["cmd_data"]
                 + ph["flash"] + ph["read_xfer"])
        ok = ~run["failed"]
        assert np.array_equal(recon[ok], run["completion"][ok])
        assert (tl["queue"] >= 0).all()
        # fixed-FC lane: no FC-availability stall outside ``wait``
        assert (ph["fc_stall"] == 0).all()

    def test_reconstruction_scout_circuit_bounds(self, tiny_cfg, tiny_txns):
        obs.enable_tracing()
        _run(tiny_cfg, tiny_txns, ("venice",))
        (run,) = obs_events.RECORDER.finalized_runs()
        assert run["is_scout"]
        tl = obs_events.derive_timeline(run)
        ((t_resv, commit_end, mask),) = [tl["occ"][0]]
        ok = ~run["failed"]
        assert np.array_equal(mask, ok)
        assert (t_resv[ok] >= tl["t0"][ok]).all()
        assert (commit_end[ok] <= run["completion"][ok]).all()


# ---------------------------------------------------------------------------
# trace export: golden schema + stream/monolithic identity
# ---------------------------------------------------------------------------


class TestTraceExport:
    def test_golden_schema(self, tiny_cfg, tiny_txns, tmp_path):
        obs.enable_tracing()
        designs = ("baseline", "venice")
        _run(tiny_cfg, tiny_txns, designs)
        with obs_spans.span("phase", "unit-test"):
            with obs_spans.span("dispatch", "group:test"):
                pass
        path = str(tmp_path / "t.trace.json")
        info = obs.export_trace(path, heatmap_csv=str(tmp_path / "h.csv"))
        summary = validate_trace(path)  # raises on any schema violation
        n_txns = len(tiny_txns["arrival"])
        assert summary["n_txn"] == n_txns * len(designs)
        assert info["n_ssd_pids"] == len(designs)
        # the planner emits its own dispatch spans on top of the two
        # explicit ones; pairs always balance
        assert summary["counts"]["B"] == summary["counts"]["E"] >= 2
        with open(path) as fh:
            doc = json.load(fh)
        pids = {e["pid"] for e in doc["traceEvents"] if e["ph"] != "M"}
        assert HARNESS_PID in pids
        assert {SSD_PID0, SSD_PID0 + 1} <= pids
        # the simulated SSD's tracks say so: "device" is left to the chip
        names = {e["pid"]: e["args"]["name"] for e in doc["traceEvents"]
                 if e["ph"] == "M" and e["name"] == "process_name"}
        assert {names[SSD_PID0], names[SSD_PID0 + 1]} == {
            f"ssd: {d} [run0]" for d in designs}
        # heatmap CSV: header + at least one nonzero utilization cell
        lines = (tmp_path / "h.csv").read_text().strip().split("\n")
        assert lines[0] == ("run,design,metric,resource,bucket,"
                            "bucket_start_us,value")
        assert len(lines) > 1

    def test_cli_validator(self, tiny_cfg, tiny_txns, tmp_path):
        from repro.obs.export import main as validate_main

        obs.enable_tracing()
        _run(tiny_cfg, tiny_txns, ("venice",))
        path = str(tmp_path / "t.trace.json")
        obs.export_trace(path)
        assert validate_main([path]) == 0
        bad = tmp_path / "bad.json"
        bad.write_text('{"traceEvents": []}')
        assert validate_main([str(bad)]) == 1

    def test_be_tie_ordering_survives_sort(self):
        """Spans sharing boundary timestamps still nest LIFO after the
        global ts sort (the _k secondary key)."""
        tracer = obs_spans.SpanTracer()
        tracer.complete("t", "outer", 100.0, 50.0)
        tracer.complete("t", "inner", 100.0, 50.0)  # identical bounds
        tracer.complete("t", "next", 150.0, 10.0)  # starts where both end
        b = TraceBuilder()
        b.add_harness_spans(tracer.drain())
        import tempfile

        with tempfile.NamedTemporaryFile("w", suffix=".json",
                                         delete=False) as fh:
            path = fh.name
        try:
            b.write(path)
            validate_trace(path)
        finally:
            os.unlink(path)

    def test_streamed_trace_identical_to_monolithic(self, tmp_path):
        """The stream-accumulated run (absolute-tick rebased windows) is
        array-identical to the monolithic recording of the same trace."""
        from repro.ssd.config import perf_optimized
        from repro.ssd.stream import stream_simulate
        from repro.workloads import load_trace

        cfg = perf_optimized(rows=2, cols=2, pages_per_block=64)
        trace = load_trace(FIXTURE)
        span_s = float(trace["arrival_us"][-1]) * 1e-6

        obs.enable_tracing()
        pages = to_pages(trace, cfg.page_bytes)
        txns = decompose_trace(cfg, pages, int(pages["footprint_pages"]))
        _run(cfg, txns, ("venice",), seed=0)
        stream_simulate(cfg, trace, ("venice",), seeds=0,
                        window_s=span_s / 4)

        runs = obs_events.RECORDER.finalized_runs()
        mono = next(r for r in runs if r["label"].startswith("run"))
        streamed = next(r for r in runs if r["label"].startswith("stream"))
        assert streamed["n"] == mono["n"] > 0
        for f in obs_events._ARRAY_FIELDS:
            assert np.array_equal(mono[f], streamed[f]), f
        assert mono["scalars"] == streamed["scalars"]
        # and the rendered SSD events agree too
        path_m = str(tmp_path / "m.json")
        bm = TraceBuilder()
        bm.add_ssd_run(mono)
        bm.write(path_m)
        path_s = str(tmp_path / "s.json")
        bs = TraceBuilder()
        bs.add_ssd_run(streamed)
        bs.write(path_s)

        def ssd_events(path):
            with open(path) as fh:
                evs = json.load(fh)["traceEvents"]
            return [{k: v for k, v in e.items() if k != "pid"}
                    for e in evs if e["ph"] != "M"]

        assert ssd_events(path_m) == ssd_events(path_s)


# ---------------------------------------------------------------------------
# heatmaps
# ---------------------------------------------------------------------------


class TestHeatmap:
    def test_bucket_matrix_preserves_totals(self):
        rng = np.random.default_rng(5)
        n = 300
        s = rng.integers(0, 10_000, n)
        e = s + rng.integers(1, 700, n)
        r = rng.integers(0, 4, n)
        bt = 64
        nb = int(e.max()) // bt + 1
        mat = bucket_matrix(s, e, r, 4, bt, nb)
        for res in range(4):
            assert mat[res].sum() == (e - s)[r == res].sum()

    def test_run_heatmap_totals_match_occupancy(self, tiny_cfg, tiny_txns):
        obs.enable_tracing()
        _run(tiny_cfg, tiny_txns, ("baseline",))
        (run,) = obs_events.RECORDER.finalized_runs()
        hm = run_heatmaps(run, bucket_ticks=256)
        tl = obs_events.derive_timeline(run)
        total = sum(int((e - s)[m].sum()) for s, e, m in tl["occ"])
        assert int(hm["util_ticks"].sum()) == total
        assert int(hm["conflicts"].sum()) == int(
            (run["conflict"] & ~run["failed"]).sum())


# ---------------------------------------------------------------------------
# satellites: scenario PERF isolation, ingest warning, check_perf gate
# ---------------------------------------------------------------------------


class TestScenarioPerfIsolation:
    def test_back_to_back_sweeps_report_independent_deltas(self, tiny_cfg):
        from repro.workloads import scenario
        from repro.workloads.scenario import QueueDepthSweep

        scn = QueueDepthSweep("hm_0", qds=(1, 4), iters=2, n_requests=40)
        first = scenario.run_queue_depth_sweeps(tiny_cfg, (scn,),
                                                ("venice",))
        d1 = scenario.last_run_perf()
        assert d1 is not None and d1["lanes"] > 0
        bench.clear_caches()  # same work both times
        second = scenario.run_queue_depth_sweeps(tiny_cfg, (scn,),
                                                 ("venice",))
        d2 = scenario.last_run_perf()
        # per-run deltas, not process-cumulative: identical work reports
        # identical counters, and the scoreboard holds the sum
        assert d2["lanes"] == d1["lanes"]
        assert d2["decomp_misses"] == d1["decomp_misses"] > 0
        assert bench.PERF["lanes"] >= d1["lanes"] + d2["lanes"]
        assert first == second  # records stay bit-identical (no perf keys)


# ---------------------------------------------------------------------------
# program spans and stage timers of a planned batch
# ---------------------------------------------------------------------------

STAGE_TIMERS = ("lower_s", "pack_s", "put_s", "fetch_s", "unpack_s")


def _requests(cfg):
    from repro.ssd.sweep_plan import RunRequest

    return [RunRequest("hm_0", cfg, ("baseline", "pssd", "venice"), 40,
                       1.5, seed) for seed in (0, 1)]


def _execute(cfg):
    """One planned batch from cold run caches: (results, PERF delta, the
    batch's new group records)."""
    from repro.ssd.sweep_plan import execute_requests

    bench.clear_caches()
    before = bench.PERF.snapshot()
    runs = execute_requests(_requests(cfg))
    groups = bench.PERF["groups"][len(before["groups"]):]
    return runs, bench.PERF.delta(before), groups


class TestProgramSpans:
    def test_tracer_clock_maps_to_epoch_ns(self):
        import time

        lo = time.time_ns()
        tr = obs_spans.SpanTracer()
        hi = time.time_ns()
        assert lo <= tr.t0_wall_ns <= hi
        assert tr.t0_wall == pytest.approx(tr.t0_wall_ns * 1e-9)
        assert tr.epoch_ns(0.0) == tr.t0_wall_ns
        assert tr.epoch_ns(1.5) == tr.t0_wall_ns + 1500
        assert tr.perf_us(tr.t0_perf + 2e-3) == pytest.approx(2e3)

    def test_interval_and_batch_tags(self):
        tr = obs.enable_tracing(recorder=False)
        assert obs_events.RECORDER is None and obs_spans.TRACER is tr
        with obs_spans.batch(7):
            obs_spans.interval("plan", "pack", tr.t0_perf + 1e-3,
                               tr.t0_perf + 3e-3, lanes=2)
            with obs_spans.batch(8):
                with obs_spans.span("exec", "run"):
                    pass
        obs_spans.instant("t", "outside")
        evs = {e[2]: e for e in tr.drain()}
        assert evs["pack"][3] == pytest.approx(1e3)
        assert evs["pack"][4] == pytest.approx(2e3)
        assert evs["pack"][5] == {"lanes": 2, "batch": 7}
        assert evs["run"][5] == {"batch": 8}
        assert evs["outside"][5] is None
        obs.disable_tracing()
        obs_spans.interval("plan", "pack", 0.0, 1.0)  # disarmed: no-op

    def test_stage_timers_lie_inside_sim_s(self, tiny_cfg):
        _, d, groups = _execute(tiny_cfg)
        assert groups
        for k in STAGE_TIMERS + ("ftl_s", "exec_s"):
            assert d[k] >= 0.0, k
        assert d["lower_s"] > 0 and d["put_s"] > 0 and d["unpack_s"] > 0
        # exec_s sums group times rounded to the millisecond
        slack = 0.0005 * len(groups)
        inside = sum(d[k] for k in ("lower_s", "pack_s", "put_s",
                                    "unpack_s", "exec_s"))
        assert inside <= d["sim_s"] + slack
        assert d["fetch_s"] <= d["exec_s"] + slack

    def test_put_bytes_is_the_bytes_of_each_groups_arguments(
            self, tiny_cfg, monkeypatch):
        import jax
        from repro.ssd import sim as S

        placed = []
        put = S._put_args

        def counting_put(args, specs, n_shards):
            placed.append(sum(
                np.asarray(a).size
                * jax.dtypes.canonicalize_dtype(np.asarray(a).dtype).itemsize
                for a in jax.tree_util.tree_leaves(args)))
            return put(args, specs, n_shards)

        monkeypatch.setattr(S, "_put_args", counting_put)
        _, d, groups = _execute(tiny_cfg)
        assert [g["put_bytes"] for g in groups] == placed
        assert d["put_bytes"] == sum(placed) > 0

    def test_spans_nest_and_carry_the_batch(self, tiny_cfg):
        tr = obs.enable_tracing(recorder=False)
        _, _, groups = _execute(tiny_cfg)
        spans = [e for e in tr.drain() if e[0] == "span"]
        names = [e[2] for e in spans]
        for stage in ("trace", "ftl", "lower", "pack", "put", "run",
                      "fetch", "unpack"):
            assert stage in names, stage
        assert names.count("run") == names.count("fetch") == len(groups)
        batches = {e[5]["batch"] for e in spans}
        assert len(batches) == 1  # one planned batch, one id

        def inside(inner, outer):
            return (outer[3] <= inner[3] and inner[3] + inner[4]
                    <= outer[3] + outer[4] and inner[6] == outer[6])

        execs = [e for e in spans if e[2].startswith("exec:")]
        assert len(execs) == len(groups)
        for stage in ("run", "fetch"):
            for e in (e for e in spans if e[2] == stage):
                assert sum(inside(e, x) for x in execs) == 1, stage
        # pack, put, exec and the lane unpack follow each other in a group
        for g in (e for e in spans if e[2].startswith("group:")):
            within = [e[2] for e in spans
                      if e is not g and inside(e, g)]
            order = [n for n in within if n in ("pack", "put", "unpack")
                     or n.startswith("exec:")]
            assert order[0] == "pack" and order[1] == "put"
            assert order[2].startswith("exec:") and order[3] == "unpack"

    def test_outputs_identical_with_span_tracer_armed(self, tiny_cfg):
        off, _, _ = _execute(tiny_cfg)
        obs.enable_tracing(recorder=False)
        on, _, _ = _execute(tiny_cfg)
        for a, b in zip(off, on):
            for d in a.results:
                ra, rb = a.results[d], b.results[d]
                for f in ("completion", "latency", "wait", "conflict",
                          "hops", "tries"):
                    assert np.array_equal(getattr(ra, f), getattr(rb, f))
                assert ra.exec_ticks == rb.exec_ticks


class TestIngestSkipWarning:
    def _write_fixture(self, path, n_bad=1):
        base = 129_000_000_000_000_000
        with open(path, "w") as f:
            for i in range(6):
                f.write(f"{base + i * 10},host,0,Read,{4096 * i},4096,0\n")
                if i < n_bad:
                    f.write(f"{base + i * 10 + 5},host,0,Write,oops,4096,0\n")

    def test_warns_once_per_file_and_counts(self, tmp_path):
        path = str(tmp_path / "corrupt.csv")
        self._write_fixture(path, n_bad=2)
        from repro.workloads.ingest import load_trace

        with pytest.warns(UserWarning, match="skipped 2 corrupted rows"):
            tr = load_trace(path, on_error="skip")
        assert tr["skipped_rows"] == 2
        # second ingest of the same file: the trace still counts its
        # drops, the warning deduplicates
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            again = load_trace(path, on_error="skip")
        assert not [w for w in caught if "corrupt.csv" in str(w.message)]
        assert again["skipped_rows"] == 2

    def test_raise_mode_untouched(self, tmp_path):
        path = str(tmp_path / "corrupt2.csv")
        self._write_fixture(path)
        from repro.workloads.ingest import load_trace

        with pytest.raises(ValueError, match="corrupted trace row"):
            load_trace(path)


class TestCheckPerf:
    def _artifact(self, total_s, phases=None, preset="smoke"):
        return {"preset": preset, "total_s": total_s,
                "phases": phases or {}, "stream": None}

    def _write(self, tmp_path, fresh, base):
        fp = tmp_path / "BENCH_fresh.json"
        bp = tmp_path / "BENCH_base.json"
        fp.write_text(json.dumps(fresh))
        bp.write_text(json.dumps(base))
        return str(fp), str(bp)

    def test_ok_exit_codes_and_summary(self, tmp_path):
        from benchmarks.check_perf import main

        fp, bp = self._write(tmp_path, self._artifact(10.0),
                             self._artifact(10.0))
        assert main([fp, bp]) == 0
        assert main([fp, bp, "--strict"]) == 0
        summary = json.loads(
            (tmp_path / "check_perf_summary.json").read_text())
        assert summary["status"] == "ok" and summary["findings"] == []

    def test_regression_gates_only_under_strict(self, tmp_path):
        from benchmarks.check_perf import main

        fp, bp = self._write(
            tmp_path,
            self._artifact(20.0, {"tail": {"s": 9.0}}),
            self._artifact(10.0, {"tail": {"s": 2.0}}))
        assert main([fp, bp]) == 0  # default stays fail-open
        assert main([fp, bp, "--strict"]) == 1
        summary = json.loads(
            (tmp_path / "check_perf_summary.json").read_text())
        assert summary["status"] == "regressed"
        kinds = {f["kind"] for f in summary["findings"]}
        assert kinds == {"total_regression", "phase_regression"}

    def test_unreadable_probe_skips(self, tmp_path):
        from benchmarks.check_perf import main

        fp, _ = self._write(tmp_path, self._artifact(1.0),
                            self._artifact(1.0))
        missing = str(tmp_path / "nope.json")
        assert main([fp, missing]) == 0
        assert main([fp, missing, "--strict"]) == 2
        summary = json.loads(
            (tmp_path / "check_perf_summary.json").read_text())
        assert summary["status"] == "skipped"
