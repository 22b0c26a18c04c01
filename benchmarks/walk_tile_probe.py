"""What a scout walk costs by the lane tile it runs in, and what the
batched scout runner's speculative retries gain by the tile they fill.

    python benchmarks/walk_tile_probe.py kernel
    python benchmarks/walk_tile_probe.py cells --tiles 16,64,128,256 \\
        --cells perf.fig9-msr,cost-16ch.fig9-wide --seed 2147483901

``kernel``: on the 8x8 and 16x8 meshes, ``--batches`` batches of ``tile``
scouts (random ends, busy maps drawn at ``--density``) walk in one jitted
scan, one ``route_dfs`` call (one walk tile) per batch.  Prints, per mesh
and tile, the seconds of the warm scan, the DFS loop iterations it ran
(each batch's longest walk) and their quotient, the cost of one loop
iteration at that tile.

``cells``: each chip benchmark cell (``chipbench/run.py``'s ``run_cell``)
with the speculative retries' tile capped at each of ``--tiles``
(``sim._TRIES_TILE``; 16 on a 16-lane group walks one try per call), one
run each, all in this process.  Prints ``sim_txn_per_s``, ``correct`` and
the scout counters of the run, warm-up sweeps included.

One JSON line per reading on standard output.  Compiles count as set-up
and are not timed; the kernel readings need a TPU (elsewhere the walk
kernel runs interpreted and the times mean nothing).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COUNTERS = ("dfs_steps_live", "dfs_steps_padded", "scout_walk_calls",
            "scout_scan_steps", "scout_tries_walked")


def kernel(tiles, batches: int, density: float, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.topology import build_mesh
    from repro.kernels.ops import route_dfs
    from repro.kernels.scout_step import link_pad, pack_tables

    interpret = jax.default_backend() != "tpu"
    for rows, cols in ((8, 8), (16, 8)):
        topo = build_mesh(rows, cols)
        tables = jnp.asarray(pack_tables(topo))
        width = link_pad(topo.n_links)
        for tile in tiles:
            g = np.random.default_rng([seed, rows, cols, tile])
            ends = g.integers(0, topo.n_nodes, (2, batches, tile), np.int32)
            busy = g.random((batches, tile, width)) < density
            busy[..., topo.n_links:] = False
            seeds = g.integers(1, 2**31 - 1, (batches, tile), np.int32)

            @jax.jit
            def walk_all(src, dst, busy, seeds):
                def one(n, x):
                    out = route_dfs(tables, *x, True, cols=cols,
                                    n_nodes=topo.n_nodes, use_pallas=True,
                                    interpret=interpret)
                    return n + out.tile_steps[0], None

                return jax.lax.scan(one, jnp.int32(0),
                                    (src, dst, busy, seeds))[0]

            args = jax.device_put((ends[0], ends[1], busy, seeds))
            iters = int(walk_all(*args))  # compile and warm
            times = []
            for _ in range(3):
                t0 = time.perf_counter()
                walk_all(*args).block_until_ready()
                times.append(time.perf_counter() - t0)
            s = min(times)
            print(json.dumps(dict(
                probe="kernel", mesh=f"{rows}x{cols}", tile=tile,
                batches=batches, density=density, seconds=s,
                loop_iterations=iters, us_per_iteration=1e6 * s / iters,
                us_per_call=1e6 * s / batches,
                device=jax.devices()[0].device_kind)), flush=True)


def cells(names, tiles, seed: int, seconds: float) -> None:
    from chipbench import run as R
    from repro.ssd import bench, sim, sweep_plan

    for name in names:
        cell = R.load_cell(name)
        for i, tile in enumerate(tiles):
            sim._TRIES_TILE = tile
            bench.clear_caches()
            sim.clear_exec_cache()
            sim._build_batched_scout_fn.cache_clear()
            sweep_plan._CAP_SEEN.clear()
            p0 = bench.PERF.snapshot()
            line = R.run_cell(cell, seed + i, seconds, False,
                              log=lambda msg: None)
            d = R.perf_delta(p0, bench.PERF.snapshot())
            c = {k: d.get(k) for k in COUNTERS}
            calls, steps = c["scout_walk_calls"], c["scout_scan_steps"]
            print(json.dumps(dict(
                probe="cells", cell=name, tile=tile, seed=seed + i,
                correct=line["correct"],
                sim_txn_per_s=line["metrics"]["sim_txn_per_s"]["value"],
                walk_calls_per_step=calls / steps if steps else None,
                **c)), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("mode", choices=("kernel", "cells"))
    ap.add_argument("--tiles", default="16,64,128,256")
    ap.add_argument("--batches", type=int, default=400)
    ap.add_argument("--density", type=float, default=0.3)
    ap.add_argument("--cells", default="perf.fig9-msr,cost.fig9-msr,"
                    "cost-16ch.fig9-wide")
    ap.add_argument("--seed", type=int, default=2147483901)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args(argv)
    tiles = [int(t) for t in args.tiles.split(",")]
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".cache", "jax"))
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from repro.xla_env import configure

    configure()
    if args.mode == "kernel":
        kernel(tiles, args.batches, args.density, args.seed)
    else:
        cells(args.cells.split(","), tiles, args.seed, args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
