"""``data.venice_io.plan_reads``: scout-reserved, link-disjoint read rounds."""
import numpy as np

from repro.data.venice_io import plan_reads


class TestData:
    def test_venice_io_plan_is_conflict_free_rounds(self):
        reqs = [(h, n) for h in range(4) for n in range(8)]
        plan = plan_reads(reqs, n_hosts=4, n_storage=32, seed=0)
        # complete coverage, each request exactly once
        assert sorted(i for r in plan.rounds for i in r) == list(range(len(reqs)))
        # within each round the reserved paths must be link-disjoint
        for rnd in plan.rounds:
            links = np.concatenate([plan.paths[i] for i in rnd])
            assert len(links) == len(set(links.tolist()))
        assert 1 <= plan.n_rounds <= len(reqs)
