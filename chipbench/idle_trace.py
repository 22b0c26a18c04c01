"""Where a cell's device idle time goes, by the program's own spans.

    python chipbench/idle_trace.py --workload perf.static-long --seed 7

Sets a cell up as ``run.py`` does (``run.Sweeper``), then runs ``--sweeps``
sweeps of its pool back to back.  Sweep ``--traced`` (the last, in a
smaller pool) runs under the device-only profiler trace of
``run.BoundedTrace`` with the program's span tracer armed alone
(``repro.obs.enable_tracing(recorder=False)``); the others run untraced.
The last line of standard output is one JSON object:

* ``sweeps``: per sweep, its time, whether it was traced, the process's
  minor and major page faults during it (``getrusage``) and the
  program's stage timers (``bench.PERF`` deltas);
* ``traced_sweep``: the traced sweep's time against the median untraced
  sweep before it (after the first, which carries one-off costs; the
  first, where it is the only one): what the armed span tracer and the
  profiler cost together;
* ``device``: ``busy_s`` / ``window_s`` of the traced window;
* ``idle_gaps``: the window's idle time named after the innermost program
  span covering it (``spanmap.reduce``), ``idle_s`` and ``unnamed_idle_s``
  (no span covers it);
* ``clock``: ``spanmap.clock_offsets`` of the device ops against the
  ``run`` spans, and where the window starts on the trace's clock;
* ``spans_s``: seconds per span name inside the traced sweep;
* ``metrics``: the cell's per-layer metrics, read by ``run.read_metric``
  from the untraced sweeps before the traced one (never traced, as the
  sweeps ``sim_txn_per_s`` times) and the trace.

``run.py`` does not arm the span tracer: this script is how the idle time
of a cell is put down to program spans.  ``--requests`` shortens the
traces, for a rehearsal on the CPU with ``--cpu``.
"""
from __future__ import annotations

import argparse
import json
import os
import resource
import statistics
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
TIMERS = ("ftl_s", "lower_s", "pack_s", "put_s", "exec_s", "fetch_s",
          "unpack_s", "sim_s")


def span_seconds(spans: list) -> dict:
    out: dict = {}
    for name, s, e, _ in spans:
        out[name] = out.get(name, 0.0) + (e - s) * 1e-9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def shorten(cell: dict, requests: int) -> dict:
    """The cell with every trace, warm-up's included, ``requests`` long."""
    tr = dict(cell["traffic"], requests_per_trace=requests)
    tr["warmup"] = [dict(ph, requests=requests) for ph in tr["warmup"]]
    return dict(cell, traffic=tr)


def faults() -> tuple:
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return ru.ru_minflt, ru.ru_majflt


def trace_cell(cell: dict, seed: int, sweeps: int, traced: int, *,
               require_tpu: bool = True, log=None) -> dict:
    from repro import obs

    from chipbench import profile, roofline, spanmap
    from chipbench import run as R

    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    _, device = R.device_info(cell["chips"], require_tpu)
    sweeper = R.Sweeper(cell, run_tag=f"it{seed}")
    bench = sweeper.bench
    sweeper.warm_up(cell)
    pool = R.plan_sweeps(cell, seed)
    n = min(sweeps, len(pool))
    traced_k = min(traced, n - 1)
    records, deltas = [], []
    for k in range(n):
        p0, f0 = bench.PERF.snapshot(), faults()
        if k == traced_k:
            tracer = obs.enable_tracing(recorder=False)
            bounded = R.BoundedTrace(R.TRACE_DIR,
                                     cell["traffic"]["trace_seconds"])
        t0 = time.perf_counter()
        sweeper.run(pool[k])
        t = time.perf_counter() - t0
        if k == traced_k:
            bounded.close()
            obs.disable_tracing()
        f1 = faults()
        d = R.perf_delta(p0, bench.PERF.snapshot())
        records.append(dict(seconds=t, traced=k == traced_k,
                            minor_faults=f1[0] - f0[0],
                            major_faults=f1[1] - f0[1],
                            stages_s={x: d.get(x) for x in TIMERS}))
        if k < traced_k:
            deltas.append((d, t))
        elif k == traced_k:
            traced_delta = d
        log(f"[sweep {k}] {t:.3f} s, {f1[0] - f0[0]} minor faults"
            f"{' (traced)' if k == traced_k else ''}")

    trace = spanmap.load(R.TRACE_DIR)
    origin = trace["profile_start_time"]
    spans = spanmap.device_clock(tracer.drain(), tracer.t0_wall_ns, origin)
    start = tracer.epoch_ns(tracer.perf_us(bounded.t0)) - origin
    window_s = bounded.t1 - bounded.t0
    if trace["devices"]:
        red = spanmap.reduce(trace, window_s, profile.kernel_patterns(),
                             spans, window_start_ns=start)
        clock = spanmap.clock_offsets(
            trace, [s for s in spans if s[0] == "run"])
    else:  # a rehearsal on the CPU, whose trace holds no device ops
        red = dict(busy_s=0.0, window_s=window_s, kernel_s={},
                   idle_gaps=[], device_ops=[], idle_s=0.0,
                   unnamed_idle_s=0.0)
        clock = {}
    clock["window_start_ns"] = start

    untraced = [t for _, t in deltas[1:]] or [t for _, t in deltas]
    untraced_s = statistics.median(untraced) if untraced else None
    ctx = dict(perf=R.sum_deltas([d for d, _ in deltas]),
               host_s=sum(t for _, t in deltas), trace=red,
               whole=bounded.whole,
               least_bytes=roofline.least_bytes(traced_delta["groups"],
                                                cell["config"]),
               peaks=(roofline.peak(device["kind"]) if require_tpu
                      else {"hbm_bytes_per_s": 1.0}))
    metrics = {}
    if deltas:
        for m in cell["per_layer"]:
            v = R.read_metric(m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = v
    device.update(busy_s=red["busy_s"], window_s=red["window_s"])
    return dict(workload=cell["name"], seed=seed, sweeps=records,
                traced_sweep=dict(traced_s=records[traced_k]["seconds"],
                                  untraced_s=untraced_s,
                                  whole=bounded.whole),
                device=device, idle_gaps=red["idle_gaps"],
                idle_s=red["idle_s"], unnamed_idle_s=red["unnamed_idle_s"],
                device_ops=red["device_ops"], clock=clock,
                spans_s=span_seconds(spans), metrics=metrics)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--sweeps", type=int, default=7)
    ap.add_argument("--traced", type=int, default=4,
                    help="index of the traced sweep")
    ap.add_argument("--requests", type=int, default=None,
                    help="requests per trace, in place of the mix's")
    ap.add_argument("--cpu", action="store_true",
                    help="run without a TPU (a rehearsal)")
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from chipbench import run as R

    cell = R.load_cell(args.workload)
    if args.requests:
        cell = shorten(cell, args.requests)
    if cell["chips"] == 1:
        for var, val in (("TPU_VISIBLE_CHIPS", "0"),
                         ("TPU_CHIPS_PER_PROCESS_BOUNDS", "1,1,1"),
                         ("TPU_PROCESS_BOUNDS", "1,1,1")):
            os.environ.setdefault(var, val)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", R.CACHE_DIR)
    from repro.xla_env import configure

    configure()
    try:
        out = trace_cell(cell, args.seed, args.sweeps, args.traced,
                         require_tpu=not args.cpu)
    except R.NoChip as e:
        print(f"idle_trace: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
