"""Chip benchmark of the SSD design-sweep simulator.

    python chipbench/run.py --workload perf.fig9-msr --seed 7 --seconds 51 --trace 0

Runs one cell of ``BENCHMARK.json`` on the accelerator this process finds
and prints, as the last line of standard output, one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown`` and ``traced_sweep``, and last
``checks``, each compared number beside its limit.

A cell is a configuration (``configs/<config>.json``: the SSD as deployed)
under a traffic mix (``traffic/<traffic>.json``: workloads, designs, trace
seeds per sweep, sweeps per run).  One *sweep* is one batch of requests,
every (workload, trace seed) of the mix times the mix's designs, handed to
the simulator exactly as its figure phases do: ``sweep_plan.prefetch``,
then ``bench.run_workload`` per request.  Traces come from the benchmark's
own generator (``tracegen.py``) and reach the simulator through
``traces.generator.register_trace`` under a name unique to the trace.

Set-up (``setup_s``, process start to window start): device check, the
mix's warm-up sweeps on traces outside the run's pool, executables from
JAX's persistent cache.  The window then runs whole sweeps back to back, starting
another only while the pool has one and the time left is at least the mean
sweep time so far.  Every run of a cell does the same pool of sweeps, in an
order drawn from ``--seed``, so runs do the same work.

``sim_txn_per_s``: simulated page transactions of every design run of every
sweep finished in the window, over the window.  ``--trace 1`` reads the
per-layer metrics (``metrics/<name>.py``) instead, from ``bench.PERF``
counter deltas over its untraced sweeps and a device-only profiler trace
of its second sweep.

``correct``: once the window has closed, a sample of the window's design
runs drawn from ``--seed`` is recomputed by the plain reference
(``reference/``) from the same trace, and every per-transaction and
per-request output must be equal (``check.py``).

Without a TPU, or with fewer chips than the cell asks for, it exits with
code 3 and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".cache", "jax")
TRACE_DIR = os.path.join(ROOT, ".cache", "chipbench-trace")
LANE_SEED_BASE = 1000


class NoChip(RuntimeError):
    pass


def since() -> float:
    """Seconds since the process started."""
    return time.perf_counter() - T_PROCESS


# ---- the cell, from data ----------------------------------------------------

def load_cell(name: str, root: str = ROOT) -> dict:
    """The cell ``name`` of ``BENCHMARK.json`` with its configuration,
    traffic mix and metric entries."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; one of {sorted(cells)}")
    cell = cells[name]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)

    def mine(metric):
        return name in metric.get("workloads", [name])

    return dict(
        name=name, chips=cell["chips"], config=config, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if mine(m)],
        per_layer=[m for m in bench["per_layer"] if mine(m)],
    )


def per_config(traffic: dict, key: str, config: str):
    """A traffic value that may be given per configuration name."""
    v = traffic[key]
    return v[config] if isinstance(v, dict) else v


def plan_sweeps(cell: dict, seed: int) -> list:
    """The run's pool of sweeps, in the order ``seed`` gives them.

    Each sweep is a list of ``(workload, trace_seed, lane_seed)``.  The pool
    is the same for every run of the cell: sweep ``k`` holds trace seeds
    ``k * per_sweep ..`` of every workload, each with its own lane seed (the
    scouts' random stream, which changes how much DFS work a Venice lane
    does).  ``seed`` orders the sweeps and draws the checked sample, so
    every run does the same work."""
    tr = cell["traffic"]
    n_sweeps = tr["sweeps"]
    per = tr["trace_seeds_per_sweep"]
    order = np.random.default_rng([seed, 0]).permutation(n_sweeps)
    return [[(w, int(k * per + j), LANE_SEED_BASE + int(k * per + j))
             for w in tr["workloads"] for j in range(per)]
            for k in order]


def trace_length(cell: dict) -> int:
    """Requests per trace: the mix's, for this configuration."""
    return per_config(cell["traffic"], "requests_per_trace",
                      cell["config"]["name"])


def warmup_sweep(cell: dict) -> list:
    """Traces outside every run's pool (trace seeds from 2**20)."""
    tr = cell["traffic"]
    return [(w, (1 << 20) + j, j) for w in tr["workloads"]
            for j in range(tr["trace_seeds_per_sweep"])]


def warmup_phases(cell: dict) -> list:
    """``(designs, requests per trace)`` of each warm-up sweep: the mix's
    ``warmup`` list runs each design set on traces just long enough to
    reach the capacity bucket the window's lanes reach."""
    conf = cell["config"]["name"]
    return [(tuple(ph["designs"]), per_config(ph, "requests", conf))
            for ph in cell["traffic"]["warmup"]]


def should_start(k: int, n_pool: int, elapsed: float, seconds: float,
                 sweep_times: list) -> bool:
    """Start sweep ``k`` of the window?  The first always; a later one
    while the pool has one and the time left covers the mean sweep."""
    if k >= n_pool:
        return False
    if k == 0:
        return True
    return seconds - elapsed >= statistics.fmean(sweep_times)


def txn_rate(txn_counts: list, window_s: float) -> float:
    """Simulated transactions completed per second of the window."""
    return float(sum(txn_counts)) / window_s


def perf_delta(before: dict, after: dict) -> dict:
    """Numeric ``bench.PERF`` counters: after minus before; ``groups``: the
    group records added in between."""
    out = {}
    for k, v in after.items():
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            out[k] = v - before.get(k, 0)
    out["groups"] = list(after.get("groups", []))[len(before.get("groups",
                                                                  [])):]
    return out


def sum_deltas(deltas: list) -> dict:
    out = {"groups": []}
    for d in deltas:
        for k, v in d.items():
            if k == "groups":
                out["groups"].extend(v)
            else:
                out[k] = out.get(k, 0) + v
    return out


def read_metric(name: str, ctx: dict):
    """The per-layer metric ``name``, by its reader ``metrics/<name>.py``;
    None where the reader finds nothing to read."""
    mod = importlib.import_module(f"chipbench.metrics.{name}")
    return mod.read(ctx)


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, device: dict, checks: dict,
                breakdown: dict | None = None,
                traced_sweep: dict | None = None) -> dict:
    line = dict(correct=bool(correct), attempted=int(attempted),
                failed=int(failed), metrics=metrics, device=device)
    if breakdown is not None:
        line["breakdown"] = breakdown
    if traced_sweep is not None:
        # how much tracing slowed the traced sweep
        line["traced_sweep"] = traced_sweep
    line["checks"] = checks  # last, as the contract asks
    return line


# ---- the run ----------------------------------------------------------------

class Sweeper:
    """Drives the simulator's entry for one sweep at a time."""

    def __init__(self, cell: dict, run_tag: str):
        from repro.ssd import bench
        from repro.ssd.config import SSDConfig
        from repro.ssd.sweep_plan import RunRequest, prefetch
        from repro.traces.generator import register_trace

        from chipbench import tracegen

        conf = cell["config"]
        self.bench, self.RunRequest, self.prefetch = bench, RunRequest, prefetch
        self.register_trace, self.tracegen = register_trace, tracegen
        self.cfg = SSDConfig(name=conf["name"], **conf["ssd"])
        self.n_req = trace_length(cell)
        self.util = conf["target_util"]
        self.designs = tuple(cell["traffic"]["designs"])
        self.tag = run_tag
        self.traces = {}  # (workload, trace_seed, n) -> byte trace

    def run(self, sweep: list, n_req: int | None = None,
            designs: tuple | None = None) -> dict:
        """One sweep; returns {(workload, trace_seed, design): SimResult}."""
        n = n_req or self.n_req
        designs = designs or self.designs
        reqs = []
        for w, ts, ls in sweep:
            trace = self.tracegen.gen_trace(w, n, ts)
            name = f"{self.tag}.{w}.{ts}.{n}"
            self.register_trace(name, trace)
            self.traces[w, ts, n] = trace
            reqs.append((w, ts, self.RunRequest(name, self.cfg, designs, n,
                                                self.util, ls)))
        self.prefetch([r for _, _, r in reqs])
        out = {}
        for w, ts, r in reqs:
            run = self.bench.run_workload(r.name, r.cfg, r.designs,
                                          r.n_requests, r.target_util, r.seed)
            for d in designs:
                out[w, ts, d] = run.results[d]
        return out

    def warm_up(self, cell: dict) -> None:
        for designs, n in warmup_phases(cell):
            self.run(warmup_sweep(cell), n_req=n, designs=designs)


def device_info(chips: int, require_tpu: bool) -> tuple:
    import jax

    devs = jax.devices()
    if require_tpu:
        if devs[0].platform != "tpu":
            raise NoChip(f"no TPU: JAX found {devs[0].platform}")
        if len(devs) < chips:
            raise NoChip(f"{len(devs)} chips visible, the cell asks for "
                         f"{chips}")
    return devs, dict(platform=devs[0].platform, kind=devs[0].device_kind,
                      count=len(devs))


def memory_peak(devs) -> int:
    peaks = []
    for d in devs:
        try:
            peaks.append(int(d.memory_stats()["peak_bytes_in_use"]))
        except (TypeError, KeyError, AttributeError, RuntimeError):
            pass
    return max(peaks) if peaks else 0


class BoundedTrace:
    """The profiler over one sweep, stopped after ``seconds`` from a timer
    thread or at the sweep's end, whichever comes first (``whole`` then
    says the trace holds the whole sweep).

    Only device ops are recorded.  Host tracing, even at its lowest level,
    records the runtime's own events too, and slowed a traced
    ``perf.static-long`` sweep to 6.4 s against 3.3 s untraced (PJRT's
    host-side transposes of the stacked tables).  The window is therefore
    timed on the host clock, from the trace's start to its stop."""

    def __init__(self, path: str, seconds: float):
        import threading

        import jax

        self._jax = jax
        self._lock = threading.Lock()
        self._on = True
        self.whole = False
        shutil.rmtree(path, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        jax.profiler.start_trace(path, profiler_options=opts)
        self.t0 = self.t1 = time.perf_counter()
        self._timer = threading.Timer(seconds, self.stop)
        self._timer.start()

    def stop(self, whole: bool = False) -> None:
        with self._lock:
            if self._on:
                self._on = False
                self.t1 = time.perf_counter()
                self.whole = whole
                self._jax.profiler.stop_trace()

    def close(self) -> None:
        """At the sweep's end."""
        self._timer.cancel()
        self.stop(whole=True)


class CompileCounter:
    """Counts executables JAX compiles or loads from its persistent cache."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in self.EVENTS:
            self.n += 1


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, log=None) -> dict:
    """Set up, warm up, measure, check.  Returns the result line."""
    import jax  # noqa: F401  (timed apart from the chip's start)

    from chipbench import check, profile, roofline

    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    log(f"[setup] {since():.3f} s: jax imported")
    devs, device = device_info(cell["chips"], require_tpu)
    log(f"[setup] {since():.3f} s: {device['platform']} {device['kind']} "
        f"x{device['count']}")
    compiles = CompileCounter()
    sweeper = Sweeper(cell, run_tag=f"cb{seed}")
    bench = sweeper.bench
    log(f"[setup] {since():.3f} s: program imported")

    for designs, n in warmup_phases(cell):
        sweeper.run(warmup_sweep(cell), n_req=n, designs=designs)
        log(f"[setup] {since():.3f} s: warm-up sweep of {len(designs)} "
            f"designs at {n} requests per trace; {compiles.n} executables "
            "compiled or loaded so far")

    pool = plan_sweeps(cell, seed)
    # the second sweep is traced: the first of a run carries one-off costs,
    # so its time is not what an untraced sweep takes
    traced_k = min(1, len(pool) - 1) if trace else -1
    results, txn_counts, sweep_times, deltas = [], [], [], []
    traced = None
    compiles_before = compiles.n
    t_window = time.perf_counter()
    setup_s = since()
    k = 0
    while k <= traced_k or should_start(k, len(pool),
                                        time.perf_counter() - t_window,
                                        seconds, sweep_times):
        p0 = bench.PERF.snapshot()
        if k == traced_k:
            tracer = BoundedTrace(TRACE_DIR, cell["traffic"]["trace_seconds"])
        t0 = time.perf_counter()
        res = sweeper.run(pool[k])
        t1 = time.perf_counter()
        if k == traced_k:
            tracer.close()
            traced = dict(delta=perf_delta(p0, bench.PERF.snapshot()),
                          seconds=t1 - t0)
            # collecting the trace is not the window's work
            t_window += time.perf_counter() - t1
        else:
            deltas.append((perf_delta(p0, bench.PERF.snapshot()), t1 - t0))
        results.append(res)
        txn_counts.append(sum(len(r.completion) for r in res.values()))
        sweep_times.append(t1 - t0)
        log(f"[sweep {k}] {t1 - t0:.3f} s, {txn_counts[-1]} transactions"
            f"{' (traced)' if k == traced_k else ''}")
        k += 1
    window_s = time.perf_counter() - t_window
    in_window = compiles.n - compiles_before
    log(f"[window] {window_s:.3f} s, {k} sweeps, {in_window} compilations "
        "inside the window")
    device["memory_peak_bytes"] = memory_peak(devs)

    metrics, breakdown, pace = {}, None, None
    if not trace:
        values = dict(sim_txn_per_s=txn_rate(txn_counts, window_s),
                      setup_s=setup_s)
        for m in cell["end_to_end"]:
            metrics[m["name"]] = dict(value=values[m["name"]],
                                      unit=m["unit"])
    else:
        red = profile.reduce(profile.load(TRACE_DIR), tracer.t1 - tracer.t0,
                             profile.kernel_patterns())
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = dict(device_ops=red["device_ops"],
                         idle_gaps=red["idle_gaps"])
        # an untraced sweep's time, to hold the traced one against: the
        # median of the sweeps after the first, or the first alone
        later = [s for i, s in enumerate(sweep_times) if i not in (0, traced_k)]
        untraced_s = statistics.median(later or sweep_times[:1])
        pace = dict(traced_s=traced["seconds"], untraced_s=untraced_s,
                    whole=tracer.whole)
        log(f"[trace] traced sweep {traced['seconds']:.3f} s, untraced "
            f"{untraced_s:.3f} s; trace holds the whole sweep: "
            f"{tracer.whole}")
        ctx = dict(
            perf=sum_deltas([d for d, _ in deltas]),
            host_s=sum(s for _, s in deltas),
            trace=red,
            whole=tracer.whole,
            least_bytes=roofline.least_bytes(traced["delta"]["groups"],
                                             cell["config"]),
            peaks=roofline.peak(device["kind"]),
        )
        for m in cell["per_layer"]:
            v = read_metric(m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = dict(value=v, unit=m["unit"])

    # correctness, once the window has closed and memory has been read
    verdict = check.check_sample(cell, seed, sweeper, results, pool, log=log)
    checks = dict(verdict["checks"])
    checks["compiles_in_window"] = dict(value=in_window, limit=0)
    correct = verdict["correct"] and in_window == 0
    for name, c in checks.items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    return result_line(correct=correct,
                       attempted=sum(len(r) for r in results),
                       failed=verdict["failed"], metrics=metrics,
                       device=device, checks=checks, breakdown=breakdown,
                       traced_sweep=pace)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    if cell["chips"] == 1:
        # one chip however many the host has (before the TPU runtime starts)
        for var, val in (("TPU_VISIBLE_CHIPS", "0"),
                         ("TPU_CHIPS_PER_PROCESS_BOUNDS", "1,1,1"),
                         ("TPU_PROCESS_BOUNDS", "1,1,1")):
            os.environ.setdefault(var, val)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", CACHE_DIR)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from repro.xla_env import configure

    configure()
    try:
        line = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"chipbench: {e}; this benchmark runs on the chip only",
              file=sys.stderr)
        return 3
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
