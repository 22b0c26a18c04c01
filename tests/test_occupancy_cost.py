"""The occupancy planner profile on the cost-optimized config's TLC timing.

On the chip every lane runs through the occupancy profile's batched
groups: the gather-free static runner for statically-routed designs and
the batched scout runner for scout designs.  Here a sweep of all nine
designs under that profile, on a 2x2 mesh of ``cost_optimized`` TLC dies,
must equal the cpu profile's flat unbatched lanes in every ``SimResult``
field, element by element, for two traces."""
import numpy as np
import pytest

from repro.ssd import DESIGNS, bench, decompose_trace
from repro.ssd import sim as S
from repro.ssd import sweep_plan as SP
from repro.ssd.config import cost_optimized
from repro.traces.generator import gen_trace, to_pages

TRACE_SEEDS = (3, 4)


def assert_same_result(a, b, ctx):
    """Every ``SimResult`` field equal, element by element and in dtype."""
    for f in S.SimResult._fields:
        x, y = getattr(a, f), getattr(b, f)
        if x is None or y is None:
            assert x is None and y is None, (ctx, f)
            continue
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype, (ctx, f)
        assert np.array_equal(x, y), (ctx, f)


def sweep_variants(cfg, txns, designs, seeds, faults=None, **planner):
    """``simulate_sweep`` without channel decomposition under the planner
    settings ``planner`` (``sweep_plan`` attributes); returns the results
    and the group variants the sweep dispatched."""
    with pytest.MonkeyPatch.context() as mp:
        for name, value in planner.items():
            mp.setattr(SP, name, value)
        g0 = len(bench.PERF["groups"])
        out = S.simulate_sweep(cfg, txns, designs, seeds=seeds,
                               decompose=False, faults=faults)
        return out, {g["variant"] for g in bench.PERF["groups"][g0:]}


# the cpu profile with the small-lane layouts off: one unbatched lane per
# shard, the flat scan every other layout is held to
FLAT = dict(PLANNER_PROFILE="cpu", SMALL_LANE_MAX_CHUNKS=0)
OCCUPANCY = dict(PLANNER_PROFILE="occupancy")


@pytest.fixture(scope="module")
def sweeps():
    """Per trace seed: the occupancy sweep and the flat sweep of every
    design, each with the variants it dispatched."""
    cfg = cost_optimized(rows=2, cols=2, pages_per_block=64)
    out = {}
    for seed in TRACE_SEEDS:
        tr = dict(gen_trace("src2_1", 60, seed=seed))
        tr["arrival_us"] = tr["arrival_us"] / 16.0  # into conflicts
        pages = to_pages(tr, cfg.page_bytes)
        txns = decompose_trace(cfg, pages,
                               footprint_pages=int(pages["footprint_pages"]))
        out[seed] = (sweep_variants(cfg, txns, DESIGNS, seed + 4,
                                    **OCCUPANCY),
                     sweep_variants(cfg, txns, DESIGNS, seed + 4, **FLAT))
    return out


@pytest.mark.parametrize("seed", TRACE_SEEDS)
@pytest.mark.parametrize("design", DESIGNS)
def test_occupancy_lane_equals_flat_lane(sweeps, design, seed):
    (occ, occ_variants), (flat, flat_variants) = sweeps[seed]
    assert occ_variants == {"batched", "bscout"}
    assert flat_variants == {"lane"}
    i = DESIGNS.index(design)
    assert occ[i].design == flat[i].design == design
    assert_same_result(occ[i], flat[i], (design, seed))
