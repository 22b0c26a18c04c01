"""Readings that set the limits of ``correct``: the program against the
reference, and the control against the program, on several seeds in one
process (one set-up).

    python chipbench/control.py --workload perf.fig9-msr --seeds 11 12 13

Per seed it runs one sweep of the cell's pool through the timed path (the
sweep the seed puts first), then compares the sampled design runs twice:
with the reference, which gives the lower reading, and with the control
(the reference at twice the clock tick), which gives the upper one.  The
last line of standard output is one JSON object with every reading.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".cache", "jax"))
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from repro.xla_env import configure

    configure()
    from chipbench import check
    from chipbench import run as R

    cell = R.load_cell(args.workload)
    try:
        R.device_info(cell["chips"], require_tpu=True)
    except R.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    sweeper = R.Sweeper(cell, run_tag="control")
    sweeper.warm_up(cell)
    readings = []
    for seed in args.seeds:
        sweeper.bench.clear_caches()
        sweeper.tag = f"control{seed}"
        pool = R.plan_sweeps(cell, seed)[:1]
        results = [sweeper.run(pool[0])]
        log = lambda m: print(m, file=sys.stderr, flush=True)
        sound = check.check_sample(cell, seed, sweeper, results, pool,
                                   log=log)
        ctrl = check.check_sample(cell, seed, sweeper, results, pool,
                                  control=True, log=log)
        r = dict(seed=seed,
                 program={k: v["value"] for k, v in sound["checks"].items()},
                 control={k: v["value"] for k, v in ctrl["checks"].items()})
        print(json.dumps(r), file=sys.stderr, flush=True)
        readings.append(r)
    print(json.dumps(dict(workload=args.workload, readings=readings)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
