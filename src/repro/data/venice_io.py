"""Venice-scheduled conflict-free parallel shard reads.

The paper's contribution — reserve a conflict-free path per transfer over a
shared interconnect before moving data — transfers directly to the cluster
storage fabric: N hosts restoring a sharded checkpoint (or prefetching data
shards) from M storage nodes over a shared fabric suffer exactly the path
conflict problem (§1) when several hosts pull from the same storage channel.

``plan_reads`` maps (host, storage-node) transfer requests onto the Venice
mesh machinery (hosts = flash controllers on the west edge; storage nodes =
flash nodes) and runs scout-based path reservation round by round: each round
is a set of transfers whose paths are mutually conflict-free; transfers that
fail reservation wait for the next round.  A loader reads the rounds in
order.
"""
from __future__ import annotations

import dataclasses
from typing import List, Sequence, Tuple

import numpy as np

from repro.core import build_mesh, scout_route_ref
from repro.core.rng import seed_for_scout


@dataclasses.dataclass
class IOPlan:
    rounds: List[List[int]]  # request indices per conflict-free round
    hops: List[int]  # path length per request
    paths: List[np.ndarray]  # reserved link ids per request
    n_conflicts: int  # reservation failures encountered while planning

    @property
    def n_rounds(self) -> int:
        return len(self.rounds)


def plan_reads(
    requests: Sequence[Tuple[int, int]],
    n_hosts: int,
    n_storage: int,
    seed: int = 0,
) -> IOPlan:
    """Schedule ``requests`` = [(host, storage_node), ...] into conflict-free
    rounds using Venice path reservation on an (n_hosts x cols) mesh."""
    cols = max(1, -(-n_storage // n_hosts))
    topo = build_mesh(n_hosts, cols)
    pending = list(range(len(requests)))
    rounds: List[List[int]] = []
    hops = [0] * len(requests)
    paths: List[np.ndarray] = [np.zeros((0,), np.int32)] * len(requests)
    conflicts = 0
    trial = 0
    while pending:
        busy = np.zeros((topo.n_links,), bool)
        this_round: List[int] = []
        still: List[int] = []
        for idx in pending:
            host, node = requests[idx]
            src = int(topo.fc_node[host % topo.rows])
            dst = int(node % topo.n_nodes)
            res = scout_route_ref(topo, src, dst, busy, seed_for_scout(seed, trial))
            trial += 1
            if res.success:
                busy[res.path_links] = True
                hops[idx] = res.hops
                paths[idx] = res.path_links
                this_round.append(idx)
            else:
                conflicts += 1
                still.append(idx)
        if not this_round:  # can't happen (empty net always routes) — guard
            this_round, still = [still[0]], still[1:]
        rounds.append(this_round)
        pending = still
    return IOPlan(rounds=rounds, hops=hops, paths=paths, n_conflicts=conflicts)
