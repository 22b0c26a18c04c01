"""Pallas scout-step kernel vs pure-jnp oracle: shape/mesh/density sweeps,
plus full-DFS replay against the scalar Algorithm-1 reference."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import build_mesh, scout_route_ref
from repro.core.rng import seed_for_scout
from repro.kernels.ops import make_route_batch
from repro.kernels.ref import scout_step_ref
from repro.kernels.scout_step import (
    STATE_W,
    B_TILE,
    VMEM_BUDGET,
    block_bytes,
    link_pad,
    pack_tables,
    scout_step_pallas,
    umod,
    xorshift32_i32,
)
from repro.ssd.designs import DESIGNS, KIND_SCOUT, REGISTRY


def _mk_batch(topo, B, density, seed):
    rs = np.random.RandomState(seed)
    n_pad = pack_tables(topo).shape[0]
    state = np.zeros((B, STATE_W), np.int32)
    state[:, 0] = rs.randint(0, topo.n_nodes, B)  # cur
    state[:, 1] = rs.randint(0, topo.n_nodes, B)  # dst
    state[:, 2] = rs.randint(-1, 4, B)  # entry
    state[:, 3] = rs.randint(1, 2**31 - 1, B)  # rng bits
    busy = np.zeros((B, link_pad(topo.n_links)), np.int32)
    busy[:, : topo.n_links] = rs.rand(B, topo.n_links) < density
    tried = np.zeros((B, 4 * n_pad), np.int32)
    tried[:, : 4 * topo.n_nodes] = rs.rand(B, 4 * topo.n_nodes) < density / 2
    return state, busy, tried


# 16x8: 232 links, past one 128-lane row of the busy map
@pytest.mark.parametrize("rows,cols",
                         [(8, 8), (4, 16), (16, 4), (4, 4), (16, 8)])
@pytest.mark.parametrize("density", [0.0, 0.3, 0.8])
def test_kernel_matches_ref_over_meshes(rows, cols, density):
    topo = build_mesh(rows, cols)
    tables = jnp.asarray(pack_tables(topo))
    B = 256
    state, busy, tried = _mk_batch(topo, B, density, rows * 31 + cols)
    got = scout_step_pallas(
        jnp.asarray(state), jnp.asarray(busy), jnp.asarray(tried), tables,
        cols=cols, n_nodes=topo.n_nodes, interpret=True, b_tile=128,
    )
    n = topo.n_nodes
    want = scout_step_ref(
        jnp.asarray(state), jnp.asarray(busy), jnp.asarray(tried),
        tables[:n, 0:4], tables[:n, 4:8], cols,
    )
    for g, w, name in zip(got, want, ["state", "busy", "tried"]):
        assert np.array_equal(np.asarray(g), np.asarray(w)), name


@pytest.mark.parametrize("rows,cols,width,fits", [
    (8, 8, 128, True), (16, 8, 256, True), (16, 16, 512, True),
    (32, 16, 1024, False)])
def test_link_map_width_and_tile_follow_the_topology(rows, cols, width, fits):
    """The busy map is the mesh's links in whole 128-lane rows, and the
    blocks of a ``B_TILE`` lane tile fit the stated VMEM budget up to a
    16x16 mesh (Mosaic compiles it for a v5e, ``test_chip_compile.py``;
    it refuses the 32x16 mesh's tile, which the kernel's assertion stops
    first)."""
    topo = build_mesh(rows, cols)
    n_pad = pack_tables(topo).shape[0]
    assert link_pad(topo.n_links) == width
    assert (block_bytes(B_TILE, width, 4 * n_pad) <= VMEM_BUDGET) == fits
    if not fits:
        state, busy, tried = _mk_batch(topo, B_TILE, 0.3, 0)
        with pytest.raises(AssertionError, match="outgrow VMEM"):
            scout_step_pallas(state, busy, tried, pack_tables(topo),
                              cols=cols, n_nodes=topo.n_nodes,
                              interpret=True)


@pytest.mark.parametrize("b_tile,B", [(128, 128), (128, 384), (256, 512)])
def test_kernel_tile_shapes(b_tile, B):
    topo = build_mesh(8, 8)
    tables = jnp.asarray(pack_tables(topo))
    state, busy, tried = _mk_batch(topo, B, 0.4, B)
    got = scout_step_pallas(
        jnp.asarray(state), jnp.asarray(busy), jnp.asarray(tried), tables,
        cols=8, n_nodes=64, interpret=True, b_tile=b_tile,
    )
    want = scout_step_ref(
        jnp.asarray(state), jnp.asarray(busy), jnp.asarray(tried),
        tables[:64, 0:4], tables[:64, 4:8], 8,
    )
    assert np.array_equal(np.asarray(got[0]), np.asarray(want[0]))


def test_kernel_minimal_only_mode():
    topo = build_mesh(8, 8)
    tables = jnp.asarray(pack_tables(topo))
    state, busy, tried = _mk_batch(topo, 128, 0.6, 5)
    got = scout_step_pallas(
        jnp.asarray(state), jnp.asarray(busy), jnp.asarray(tried), tables,
        cols=8, n_nodes=64, interpret=True, b_tile=128, allow_nonminimal=False,
    )
    # in minimal-only mode no step may be a misroute
    assert int(np.asarray(got[0])[:, 6].sum()) == 0


def test_umod_matches_python_unsigned():
    xs = np.array([0, 1, 2**31 - 1, -1, -2**31, 12345, -98765], np.int32)
    for m in [1, 2, 3, 4]:
        got = np.asarray(umod(jnp.asarray(xs), jnp.int32(m)))
        want = np.array([(int(x) & 0xFFFFFFFF) % m for x in xs], np.int32)
        assert np.array_equal(got, want), (m, got, want)


def test_xorshift_matches_python():
    from repro.core.rng import xorshift32_py

    xs = np.array([1, 7, 2**31 - 1, -5, 123456789], np.int32)
    got = np.asarray(xorshift32_i32(jnp.asarray(xs))).astype(np.uint32)
    want = np.array([xorshift32_py(int(x) & 0xFFFFFFFF) for x in xs], np.uint32)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("design", DESIGNS)
def test_kernel_ref_parity_per_design(design):
    """jnp-reference vs Pallas-interpret parity under each registered
    design's routing knobs.  Statically-routed designs never walk the
    mesh — their scout degenerates to a dst == src (zero-length) walk —
    so their batches pin the degenerate path; scout designs pin their
    ``allow_nonminimal`` setting over a half-busy mesh."""
    spec = REGISTRY[design]
    topo = build_mesh(8, 8)
    tables = jnp.asarray(pack_tables(topo))
    B = 128
    state, busy, tried = _mk_batch(topo, B, 0.5, DESIGNS.index(design) + 11)
    if spec.kind != KIND_SCOUT:
        state[:, 1] = state[:, 0]  # degenerate walk: already at destination
    got = scout_step_pallas(
        jnp.asarray(state), jnp.asarray(busy), jnp.asarray(tried), tables,
        cols=8, n_nodes=64, allow_nonminimal=spec.allow_nonminimal,
        interpret=True, b_tile=64,
    )
    want = scout_step_ref(
        jnp.asarray(state), jnp.asarray(busy), jnp.asarray(tried),
        tables[:64, 0:4], tables[:64, 4:8], 8,
        allow_nonminimal=spec.allow_nonminimal,
    )
    for g, w, name in zip(got, want, ["state", "busy", "tried"]):
        assert np.array_equal(np.asarray(g), np.asarray(w)), (design, name)
    if spec.kind != KIND_SCOUT:
        assert (np.asarray(got[0])[:, 4] == 2).all(), design  # all arrived


def test_kernel_degenerate_dst_eq_src_is_noop():
    """A scout already at its destination must arrive (flags == 2) without
    moving, claiming a link, or burning RNG state."""
    topo = build_mesh(4, 4)
    tables = jnp.asarray(pack_tables(topo))
    state, busy, tried = _mk_batch(topo, 64, 0.7, 21)
    state[:, 1] = state[:, 0]
    got = scout_step_pallas(
        jnp.asarray(state), jnp.asarray(busy), jnp.asarray(tried), tables,
        cols=4, n_nodes=16, interpret=True, b_tile=64,
    )
    s = np.asarray(got[0])
    assert (s[:, 4] == 2).all()  # flags: arrived
    assert np.array_equal(s[:, 0], state[:, 0])  # no movement
    assert np.array_equal(s[:, 3], state[:, 3])  # RNG untouched
    assert np.array_equal(np.asarray(got[1]), busy)
    assert np.array_equal(np.asarray(got[2]), tried)


@pytest.mark.parametrize("rows,cols", [(8, 8), (16, 8)])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_full_dfs_replay_matches_scalar_reference(use_pallas, rows, cols):
    topo = build_mesh(rows, cols)
    rs = np.random.RandomState(3)
    B = 48
    src = np.array([int(topo.fc_node[rs.randint(rows)]) for _ in range(B)],
                   np.int32)
    dst = rs.randint(0, topo.n_nodes, B).astype(np.int32)
    busy = rs.rand(B, topo.n_links) < rs.uniform(0, 0.7, (B, 1))
    seeds = np.array([seed_for_scout(9, i) for i in range(B)], np.uint32)
    route = make_route_batch(topo, use_pallas=use_pallas, interpret=True)
    out = route(jnp.asarray(src), jnp.asarray(dst), jnp.asarray(busy),
                jnp.asarray(seeds))
    for i in range(B):
        ref = scout_route_ref(topo, int(src[i]), int(dst[i]), busy[i].copy(),
                              int(seeds[i]))
        assert bool(out.success[i]) == ref.success
        assert int(out.steps[i]) == ref.steps
        if ref.success:
            mask = np.zeros(topo.n_links, bool)
            mask[ref.path_links] = True
            assert np.array_equal(
                np.asarray(out.path_mask[i, : topo.n_links]), mask
            )
            assert int(out.hops[i]) == ref.hops
            assert int(out.misroutes[i]) == ref.misroutes
