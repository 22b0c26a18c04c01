"""The benchmark's copy of the trace generator stays bit-equal to the
simulator's for every workload and trace seed the cells use."""
import json
import os

import numpy as np
import pytest

from chipbench import run as R
from chipbench import tracegen

FIELDS = ("arrival_us", "is_read", "offset_bytes", "size_bytes",
          "footprint_bytes")


def _cell_traces():
    root = R.ROOT
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        cells = [w["name"] for w in json.load(f)["workloads"]]
    seen = set()
    for name in cells:
        cell = R.load_cell(name, root)
        n = R.trace_length(cell)
        for w, ts, _ in (t for sw in R.plan_sweeps(cell, 0) for t in sw):
            seen.add((w, ts, n))
        for _, warm_n in R.warmup_phases(cell):
            for w, ts, _ in R.warmup_sweep(cell):
                seen.add((w, ts, warm_n))
    return sorted(seen)


CASES = _cell_traces()


def test_cells_have_traces():
    assert len(CASES) > 100


@pytest.mark.parametrize("workload", sorted({w for w, _, _ in CASES}))
def test_bit_equal_to_program(workload):
    from repro.traces.generator import gen_trace

    for w, seed, n in CASES:
        if w != workload:
            continue
        mine, theirs = tracegen.gen_trace(w, n, seed), gen_trace(w, n, seed)
        for f in FIELDS:
            assert np.array_equal(mine[f], theirs[f]), (w, seed, n, f)


def test_accelerate_and_pages_match_program():
    from repro.ssd import bench
    from repro.ssd.config import cost_optimized
    from repro.traces.generator import to_pages

    cfg = cost_optimized()
    tr = tracegen.gen_trace("proj_3", 500, 4)
    mine = tracegen.accelerate(tr, cfg.chan_gbps, cfg.rows, 1.5)
    theirs, factor = bench.accelerate(tr, cfg, 1.5)
    assert factor > 1.0
    assert np.array_equal(mine["arrival_us"], theirs["arrival_us"])
    a, b = tracegen.to_pages(mine, cfg.page_bytes), to_pages(theirs,
                                                            cfg.page_bytes)
    for k in ("offset_page", "n_pages", "footprint_pages"):
        assert np.array_equal(a[k], b[k])
