"""Record the small profiler trace that the trace-reduction test reads.

    python chipbench/record_testdata.py        # on the chip

Runs one tiny sweep (two workloads, 12 requests, one static design and
Venice, perf configuration) through the same entry as the benchmark, traced
as the benchmark traces a sweep (``run.BoundedTrace``), and writes
the gzipped XSpace to ``chipbench/testdata/sweep.xplane.pb.gz`` together
with the sweep's ``bench.PERF`` group records (``sweep_groups.json``).
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
OUT = os.path.join(BENCH, "testdata")


def main() -> int:
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".cache", "jax"))
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from repro.xla_env import configure

    configure()
    import jax

    from chipbench import run as R

    if jax.devices()[0].platform != "tpu":
        print("record_testdata: no TPU", file=sys.stderr)
        return 3
    cell = R.load_cell("perf.fig9-msr")
    cell["traffic"] = dict(cell["traffic"], workloads=["hm_0", "proj_3"],
                           designs=["baseline", "venice"],
                           trace_seeds_per_sweep=1, requests_per_trace=12,
                           warmup=[{"designs": ["baseline", "venice"],
                                    "requests": 12}])
    sweeper = R.Sweeper(cell, run_tag="testdata")
    sweeper.warm_up(cell)
    before = sweeper.bench.PERF.snapshot()
    tmp = os.path.join(ROOT, ".cache", "chipbench-testdata")
    tracer = R.BoundedTrace(tmp, 60.0)
    sweeper.run(R.plan_sweeps(cell, 0)[0])
    tracer.close()
    delta = R.perf_delta(before, sweeper.bench.PERF.snapshot())
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    os.makedirs(OUT, exist_ok=True)
    with open(src, "rb") as f, gzip.open(
            os.path.join(OUT, "sweep.xplane.pb.gz"), "wb") as g:
        g.write(f.read())
    with open(os.path.join(OUT, "sweep_groups.json"), "w") as f:
        json.dump(dict(device_kind=jax.devices()[0].device_kind,
                       groups=delta["groups"]), f, indent=1)
    shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps(dict(ok=True, groups=len(delta["groups"]))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
