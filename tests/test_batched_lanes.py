"""Small-lane layouts: batched (gather-free) and stacked, pinned bit-exact.

PR 3 recorded "vmap-batching lanes is ~50x slower and therefore unused";
this PR revisits that with the gather-free formulation (one-hot
compare-and-reduce state lookups, node tables gathered per transaction
once a chunk, outside the step, masked-arithmetic validity — see
``sim._make_batched_static_step``).  The
runner must be bit-identical to the flat unbatched scan for EVERY
statically-routed design — including nossd, whose live FC selection takes
the one-hot F-axis path — and the stacked layout (K sequential unbatched
lanes per shard) must be bit-identical for every design incl. scouts.
The planner's layout choice is pure policy; these tests force each layout
regardless of the measured-threshold policy in ``sweep_plan``.
"""
import types

import jax
import numpy as np
import pytest

from repro.ssd import DESIGNS, bench, perf_optimized, simulate
from repro.ssd import sim as S
from repro.ssd import sweep_plan as SP
from repro.ssd.designs import (REGISTRY, KIND_SCOUT, LaneTables,
                               lower_designs, node_tables,
                               pregather_node_tables)

PARITY_FIELDS = ("completion", "wait", "conflict", "hops", "tries",
                 "misroutes")
STATIC_DESIGNS = tuple(d for d in DESIGNS
                       if REGISTRY[d].kind != KIND_SCOUT)
SCOUT_DESIGNS = tuple(d for d in DESIGNS
                      if REGISTRY[d].kind == KIND_SCOUT)


def _assert_parity(lane, solo, ctx):
    for f in PARITY_FIELDS:
        assert np.array_equal(getattr(lane, f), getattr(solo, f)), (ctx, f)
    assert lane.bus_hold_ticks == solo.bus_hold_ticks, ctx
    assert lane.link_hold_ticks == solo.link_hold_ticks, ctx


def _variants(monkeypatch, layout):
    """Force every small-lane-eligible pool onto one layout."""
    monkeypatch.setattr(SP, "SMALL_LANE_MAX_CHUNKS", 64)
    monkeypatch.setattr(SP, "_BATCH_MIN_LANES", 2)
    if layout == "batched":
        monkeypatch.setattr(SP, "_BATCH_MAX_PER_SHARD", 64)
    else:  # stack only
        monkeypatch.setattr(SP, "_BATCH_MAX_PER_SHARD", 0)


def test_batched_runner_every_static_design(tiny_cfg, tiny_txns,
                                            monkeypatch):
    """One batched dispatch spanning ALL statically-routed designs
    (heterogeneous scalars, pnssd's 2-candidate masks, nossd's dynamic
    FC) == per-design flat ``simulate``, bit for bit."""
    _variants(monkeypatch, "batched")
    g0 = len(bench.PERF["groups"])
    sweep = S.simulate_sweep(tiny_cfg, tiny_txns, STATIC_DESIGNS, seeds=5,
                             decompose=False)
    new = bench.PERF["groups"][g0:]
    assert {g["variant"] for g in new} == {"batched"}
    assert len(new) == 1  # the whole static sweep was ONE dispatch
    for lane, design in zip(sweep, STATIC_DESIGNS):
        _assert_parity(lane, simulate(tiny_cfg, tiny_txns, design, seed=5),
                       design)


@pytest.mark.parametrize("design", STATIC_DESIGNS)
def test_batched_runner_per_design_seed_sweep(tiny_cfg, tiny_txns, design,
                                              monkeypatch):
    """A homogeneous batch (same design, several seeds) stays bit-exact —
    covers the promoted/specialized scalar paths per design kind."""
    _variants(monkeypatch, "batched")
    lanes = (design,) * 6  # wider than the 2*n_shards small-lane window
    sweep = S.simulate_sweep(tiny_cfg, tiny_txns, lanes, seeds=(3,) * 6,
                             decompose=False)
    solo = simulate(tiny_cfg, tiny_txns, design, seed=3)
    for lane in sweep:
        _assert_parity(lane, solo, design)


def test_batched_mixed_lengths_masked_tail(tiny_cfg, tiny_txns,
                                           monkeypatch):
    """Lanes of different lengths share a batch: the shorter lane's
    masked tail steps must not perturb it (validity masking == the
    unbatched cond-skip)."""
    from repro.ssd.sweep_plan import execute_sim_runs

    _variants(monkeypatch, "batched")
    short = {k: np.asarray(v)[: len(tiny_txns["arrival"]) // 3]
             for k, v in dict(tiny_txns).items()}
    runs = [
        (tiny_cfg, tiny_txns, ("baseline", "pnssd", "pssd"), (5, 5, 5),
         False),
        (tiny_cfg, short, ("nossd", "ideal"), (5, 5), False),
    ]
    res_long, res_short = execute_sim_runs(runs)
    _assert_parity(res_long[0], simulate(tiny_cfg, tiny_txns, "baseline",
                                         seed=5), "baseline")
    _assert_parity(res_long[1], simulate(tiny_cfg, tiny_txns, "pnssd",
                                         seed=5), "pnssd")
    _assert_parity(res_long[2], simulate(tiny_cfg, tiny_txns, "pssd",
                                         seed=5), "pssd")
    _assert_parity(res_short[0], simulate(tiny_cfg, short, "nossd",
                                          seed=5), "nossd")
    _assert_parity(res_short[1], simulate(tiny_cfg, short, "ideal",
                                          seed=5), "ideal")


def test_stacked_lanes_every_design(tiny_cfg, tiny_txns, monkeypatch):
    """The stacked layout (sequential unbatched lanes per shard) is
    bit-exact for every design, scouts included."""
    _variants(monkeypatch, "stack")
    g0 = len(bench.PERF["groups"])
    sweep = S.simulate_sweep(tiny_cfg, tiny_txns, DESIGNS, seeds=5,
                             decompose=False)
    new = bench.PERF["groups"][g0:]
    assert "stack" in {g["variant"] for g in new}
    assert len(new) < len(DESIGNS)  # dispatches actually collapsed
    for lane, design in zip(sweep, DESIGNS):
        _assert_parity(lane, simulate(tiny_cfg, tiny_txns, design, seed=5),
                       design)


def test_scout_stack_parity_with_kscout(tiny_cfg, tiny_txns, monkeypatch):
    """Stacked scout lanes with heterogeneous n_scouts (k_max=3 pool):
    the 1-scout lanes must stay bit-identical to their solo runs."""
    _variants(monkeypatch, "stack")
    designs = ("venice", "venice_kscout", "venice_minimal", "venice_hold",
               "venice", "venice_kscout")
    sweep = S.simulate_sweep(tiny_cfg, tiny_txns, designs, seeds=9,
                             decompose=False)
    for lane, design in zip(sweep, designs):
        _assert_parity(lane, simulate(tiny_cfg, tiny_txns, design, seed=9),
                       design)


def test_default_policy_collapses_small_pools(tiny_cfg, tiny_txns):
    """Under the DEFAULT policy (no monkeypatching), a small-lane static
    pool wider than the batched window still collapses into stacked
    dispatches — the tail-phase regime."""
    designs = STATIC_DESIGNS * 3  # 15 small static lanes on 2 shards
    g0 = len(bench.PERF["groups"])
    sweep = S.simulate_sweep(tiny_cfg, tiny_txns, designs,
                             seeds=tuple(range(15)), decompose=False)
    new = bench.PERF["groups"][g0:]
    assert len(new) <= 2, [g["variant"] for g in new]
    for lane, design, seed in zip(sweep, designs, range(15)):
        _assert_parity(lane, simulate(tiny_cfg, tiny_txns, design,
                                      seed=seed), design)


def _table_rows(cfg, designs):
    """Per-design :class:`LaneTables` rows (numpy, no lane axis)."""
    tables = lower_designs(cfg, designs)
    return [LaneTables(*(np.asarray(a)[i] for a in tables))
            for i in range(len(designs))]


@pytest.mark.parametrize("geom", [(2, 2), (3, 4)])
@pytest.mark.parametrize("design", STATIC_DESIGNS)
def test_device_gather_equals_host_pregather(tiny_cfg, tiny_txns, design,
                                             geom):
    """The batched run's per-chunk gather of the per-lane node tables
    reads, for every valid slot, exactly the rows the host pregather
    resolves (all five tables).  The lanes mix designs so a wrong lane
    offset reads another lane's tables; padding slots read node 0."""
    cfg = (tiny_cfg if geom == (2, 2)
           else perf_optimized(rows=geom[0], cols=geom[1],
                               pages_per_block=64))
    others = tuple(d for d in STATIC_DESIGNS if d != design)
    designs = (design,) + others[:2]
    rows = _table_rows(cfg, designs)
    n_nodes = cfg.rows * cfg.cols
    rng = np.random.default_rng(7)
    C = 256
    lengths = (C, C // 3, 0)  # a full lane, a short one, an empty one
    node = np.zeros((C, len(designs)), np.int32)
    valid = np.zeros((C, len(designs)), bool)
    for b, n in enumerate(lengths):
        node[:n, b] = rng.integers(0, n_nodes, n)
        valid[:n, b] = True
    if geom == (2, 2):  # the planner's own nodes on the tiny config
        n0 = min(C, len(tiny_txns["node"]))
        node[:n0, 0] = np.asarray(tiny_txns["node"])[:n0]
    nt = S.BatchNodeTables(*(
        np.stack([node_tables(r)[f] for r in rows])
        for f in S.BatchNodeTables._fields))
    got = jax.jit(lambda nt, node: S._gather_node_rows(S._node_rows(nt),
                                                       node))(nt, node)
    for b, r in enumerate(rows):
        want = pregather_node_tables(r, node[:, b])
        row0 = pregather_node_tables(r, np.zeros(C, np.int32))
        for f in S.BatchNodeTables._fields:
            g = np.asarray(getattr(got, f))[:, b].reshape(want[f].shape)
            v = valid[:, b]
            assert np.array_equal(g[v], want[f][v]), (design, geom, b, f)
            assert np.array_equal(g[~v], row0[f][~v]), (design, geom, b, f)


def test_put_bytes_are_the_per_lane_tables(tiny_cfg, tiny_txns, monkeypatch):
    """Short lanes at a larger capacity: a batched group places its
    scalars, its time-major transactions, its per-lane node tables (N
    rows a lane, never ``cap``) and its chunk counts, and ``put_bytes``
    counts exactly those leaves.  That is below what the node tables
    alone weigh pregathered per slot ``[cap, B, ...]``."""
    from repro.ssd.designs import mask_words_per_row, sweep_layout

    _variants(monkeypatch, "batched")
    sig = S._geom_sig(tiny_cfg)
    cap = 4 * S._pad_to(len(tiny_txns["arrival"]))
    monkeypatch.setitem(SP._CAP_SEEN, ("small", sig), cap)
    designs = STATIC_DESIGNS + ("baseline",)  # 6 lanes: 3 a shard, no pads
    B = len(designs)
    g0 = len(bench.PERF["groups"])
    b0 = bench.PERF["put_bytes"]
    S.simulate_sweep(tiny_cfg, tiny_txns, designs, seeds=tuple(range(B)),
                     decompose=False)
    (g,) = bench.PERF["groups"][g0:]
    assert (g["variant"], g["capacity"], g["lanes"]) == ("batched", cap, B)
    lay = sweep_layout(tiny_cfg)
    F0, R, N = lay.F_pad, lay.R_pad, lay.n_nodes
    W = mask_words_per_row(R)
    node_row = 4 * (F0 * 2 * W + F0 * 2 + F0 + 2) + 1  # + cand2 (bool)
    scal = sum(np.dtype(S._TABLE_SCALAR_DTYPES[k]).itemsize
               for k in S._PROMOTABLE) + F0 + R  # + fc_valid, res_dead
    txn = 7 * 4 + 1  # seven int32 fields and the valid flag
    want = B * (scal + cap * txn + N * node_row + 4)  # + n_chunks
    assert g["put_bytes"] == want
    assert bench.PERF["put_bytes"] - b0 == want
    assert g["put_bytes"] < cap * B * node_row


@pytest.mark.parametrize("cap_mult", [1, 4])
def test_time_major_stack_equals_pad_and_stack(tiny_cfg, tiny_txns,
                                               cap_mult):
    """Both batched branches (static and scout) stack their lanes'
    transactions time-major through ``_stack_txns_time_major``: the same
    arrays, dtypes included, as padding a copy per lane with ``_pad_txns``
    and stacking the copies on axis 1, for lanes of unequal length."""
    full = S._pack_txns(tiny_cfg, tiny_txns,
                        np.arange(len(tiny_txns["arrival"])), None)[0]
    n = len(full.node)
    lanes = [types.SimpleNamespace(txns=SP._slice_txns(full, np.arange(k)))
             for k in (n, n // 2, 1, n)]
    cap = cap_mult * S._pad_to(n)
    got = SP._stack_txns_time_major(lanes, cap)
    want = [np.stack(cols, axis=1) for cols in
            zip(*(SP._pad_txns(ln.txns, cap) for ln in lanes))]
    for f, g, w in zip(S.TxnArrays._fields, got, want):
        assert g.dtype == w.dtype and np.array_equal(g, w), f
