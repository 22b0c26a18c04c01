"""The occupancy profile's batched static runner under injected faults.

Dead links, FCs and routers ride the batched runner's ``res_dead`` mask
and the read-retry ladder stretches the packed op ticks.  For each
statically-routed design under each fault spec of ``test_faults``, the
occupancy sweep must equal the cpu profile's flat unbatched lane in every
``SimResult`` field."""
import pytest

from repro.ssd.designs import static_design_names
from test_faults import SPECS
from test_occupancy_cost import FLAT, OCCUPANCY, assert_same_result, \
    sweep_variants

STATIC_DESIGNS = static_design_names()


@pytest.fixture(scope="module")
def sweeps(tiny_cfg, tiny_txns):
    return {
        name: (sweep_variants(tiny_cfg, tiny_txns, STATIC_DESIGNS, 5,
                              faults=spec, **OCCUPANCY),
               sweep_variants(tiny_cfg, tiny_txns, STATIC_DESIGNS, 5,
                              faults=spec, **FLAT))
        for name, spec in SPECS.items()
    }


@pytest.mark.parametrize("spec_name", tuple(SPECS))
@pytest.mark.parametrize("design", STATIC_DESIGNS)
def test_occupancy_batched_equals_flat_lane(sweeps, design, spec_name):
    (occ, occ_variants), (flat, flat_variants) = sweeps[spec_name]
    assert occ_variants == {"batched"}
    assert flat_variants == {"lane"}
    i = STATIC_DESIGNS.index(design)
    assert_same_result(occ[i], flat[i], (design, spec_name))
