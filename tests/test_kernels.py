"""The scout decision step vs the pure-jnp oracle: shape/mesh/density
sweeps; the walk kernel vs the same walk as XLA ops; full-DFS replay
against the scalar Algorithm-1 reference."""
import functools

import jax
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import build_mesh, scout_route_ref
from repro.core.rng import seed_for_scout
from repro.kernels.batched_step import B_TILE
from repro.kernels.ops import make_route_batch, route_dfs
from repro.kernels.ref import scout_step_ref
from repro.kernels.scout_step import (
    STATE_W,
    VMEM_BUDGET,
    block_bytes,
    link_pad,
    pack_tables,
    scout_walk_pallas,
    step_math,
    umod,
    walk_lanes,
    walk_tile,
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


@functools.partial(jax.jit, static_argnames=("cols", "n_nodes",
                                             "allow_nonminimal"))
def _step(state, busy, tried, tables, *, cols, n_nodes,
          allow_nonminimal=True):
    """``step_math``, the decision the walk kernel runs every iteration."""
    return step_math(state, busy, tried, tables[:n_nodes, 0:4],
                     tables[:n_nodes, 4:8], cols, allow_nonminimal)


# 16x8: 232 links, past one 128-lane row of the busy map
@pytest.mark.parametrize("rows,cols",
                         [(8, 8), (4, 16), (16, 4), (4, 4), (16, 8)])
@pytest.mark.parametrize("density", [0.0, 0.3, 0.8])
def test_kernel_matches_ref_over_meshes(rows, cols, density):
    topo = build_mesh(rows, cols)
    tables = jnp.asarray(pack_tables(topo))
    B = 256
    state, busy, tried = _mk_batch(topo, B, density, rows * 31 + cols)
    got = _step(
        jnp.asarray(state), jnp.asarray(busy), jnp.asarray(tried), tables,
        cols=cols, n_nodes=topo.n_nodes,
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
    walk kernel's blocks, stacks included, fit the stated VMEM budget at
    a 128-lane tile (the tile a 16x16 mesh takes) up to a 16x16 mesh; the
    32x16 mesh's do not, and the kernel's assertion stops them before
    Mosaic sees them."""
    topo = build_mesh(rows, cols)
    tile = B_TILE // 2
    assert link_pad(topo.n_links) == width
    assert (block_bytes(tile, width, topo.n_nodes) <= VMEM_BUDGET) == fits
    if not fits:
        state, busy, _ = _mk_batch(topo, tile, 0.3, 0)
        with pytest.raises(AssertionError, match="outgrow VMEM"):
            scout_walk_pallas(state, busy, pack_tables(topo), cols=cols,
                              n_nodes=topo.n_nodes, interpret=True,
                              b_tile=tile)


@pytest.mark.parametrize("b_tile,B", [(128, 128), (128, 384), (256, 512)])
def test_kernel_tile_shapes(b_tile, B):
    """The walk kernel over one and several lane tiles walks every scout
    as the XLA walk of the whole batch does, and counts each tile's own
    loop: its longest walk."""
    topo = build_mesh(8, 8)
    tables = jnp.asarray(pack_tables(topo))
    src, dst, busy, seeds = _walk_batch(topo, B, 0.4, B)
    state = np.zeros((B, STATE_W), np.int32)
    state[:, 0], state[:, 1], state[:, 2] = src, dst, -1
    state[:, 3] = seeds.view(np.int32)
    busy = np.pad(busy, ((0, 0), (0, link_pad(topo.n_links) - topo.n_links)))
    state, busy = jnp.asarray(state), jnp.asarray(busy, jnp.int32)
    res, path = scout_walk_pallas(state, busy, tables, cols=8, n_nodes=64,
                                  interpret=True, b_tile=b_tile)
    want_res, want_path = walk_lanes(state, busy, tables[:64, 0:4],
                                     tables[:64, 4:8], 8, True)
    res, want_res = np.asarray(res), np.asarray(want_res)
    assert np.array_equal(res[:, :4], want_res[:, :4])
    assert np.array_equal(np.asarray(path), np.asarray(want_path))
    tiles = res[:, 2].reshape(-1, b_tile).max(axis=1)
    assert np.array_equal(res[:, 4], np.repeat(tiles, b_tile))
    assert (want_res[:, 4] == want_res[:, 2].max()).all()


def test_kernel_minimal_only_mode():
    topo = build_mesh(8, 8)
    tables = jnp.asarray(pack_tables(topo))
    state, busy, tried = _mk_batch(topo, 128, 0.6, 5)
    got = _step(
        jnp.asarray(state), jnp.asarray(busy), jnp.asarray(tried), tables,
        cols=8, n_nodes=64, allow_nonminimal=False,
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
    """jnp-reference vs ``step_math`` parity under each registered
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
    got = _step(
        jnp.asarray(state), jnp.asarray(busy), jnp.asarray(tried), tables,
        cols=8, n_nodes=64, allow_nonminimal=spec.allow_nonminimal,
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
    got = _step(
        jnp.asarray(state), jnp.asarray(busy), jnp.asarray(tried), tables,
        cols=4, n_nodes=16,
    )
    s = np.asarray(got[0])
    assert (s[:, 4] == 2).all()  # flags: arrived
    assert np.array_equal(s[:, 0], state[:, 0])  # no movement
    assert np.array_equal(s[:, 3], state[:, 3])  # RNG untouched
    assert np.array_equal(np.asarray(got[1]), busy)
    assert np.array_equal(np.asarray(got[2]), tried)


@pytest.mark.parametrize("rows,cols,B,tile", [
    (8, 8, 16, 16), (16, 8, 16, 16), (8, 8, 512, 256), (16, 8, 512, 256),
    (16, 16, 512, 128), (8, 8, 37, 8)])
def test_walk_tile_follows_the_shapes(rows, cols, B, tile):
    """The walk kernel's lane tile is the batch's largest lane tile whose
    blocks, the DFS stacks included, fit ``VMEM_BUDGET``: one 16-lane tile
    for a Fig. 9 group on 8x8 and 16x8; a 16x16 mesh's 256-lane tile would
    outgrow the budget with its stacks, so it shrinks to 128."""
    topo = build_mesh(rows, cols)
    W = link_pad(topo.n_links)
    assert walk_tile(B, W, topo.n_nodes) == tile
    assert block_bytes(tile, W, topo.n_nodes) <= VMEM_BUDGET
    if tile < min(B, B_TILE) and B % (2 * tile) == 0:
        assert block_bytes(2 * tile, W, topo.n_nodes) > VMEM_BUDGET


def _walk_batch(topo, B, density, seed):
    rs = np.random.RandomState(seed)
    src = np.array([int(topo.fc_node[rs.randint(topo.rows)])
                    for _ in range(B)], np.int32)
    dst = rs.randint(0, topo.n_nodes, B).astype(np.int32)
    busy = rs.rand(B, topo.n_links) < density
    seeds = np.array([seed_for_scout(seed, i) for i in range(B)], np.uint32)
    return src, dst, busy, seeds


def _assert_same_walks(got, want, ctx):
    for f in got._fields:
        if f != "tile_steps":  # each backend tiles its own loop
            assert np.array_equal(np.asarray(getattr(got, f)),
                                  np.asarray(getattr(want, f))), (ctx, f)


# B = 37 pads to 40: five 8-lane tiles, the last part padding
@pytest.mark.parametrize("rows,cols", [(8, 8), (16, 8), (16, 5)])
@pytest.mark.parametrize("density", [0.0, 0.35, 0.7])
@pytest.mark.parametrize("allow", ["adaptive", "minimal", "per-lane"])
def test_walk_kernel_matches_xla_walk(rows, cols, density, allow):
    """The walk kernel (interpret mode) and XLA ops run the same
    walk: every ``BatchRouteOut`` field equal, element by element, with
    two dead links, a static or a per-lane ``allow_nonminimal``, and a
    batch that is not a multiple of the lane tile."""
    topo = build_mesh(rows, cols)
    B = 37
    src, dst, busy, seeds = _walk_batch(topo, B, density, rows * 7 + cols)
    dead = np.zeros(topo.n_links, bool)
    dead[[0, topo.n_links // 2]] = True
    args = tuple(jnp.asarray(a) for a in (src, dst, busy, seeds))
    if allow == "per-lane":
        flags = jnp.asarray(np.random.RandomState(B).rand(B) < 0.5)
        tables = jnp.asarray(pack_tables(topo))

        def run(use_pallas):
            fn = functools.partial(route_dfs, cols=cols,
                                   n_nodes=topo.n_nodes,
                                   use_pallas=use_pallas, interpret=True)
            return jax.jit(fn)(tables, *args[:2], args[2] | dead, args[3],
                               flags)
    else:
        def run(use_pallas):
            return make_route_batch(
                topo, use_pallas=use_pallas, interpret=True,
                allow_nonminimal=allow == "adaptive", dead_links=dead)(*args)
    got, want = run(True), run(False)
    _assert_same_walks(got, want, (rows, cols, density, allow))
    # each 8-lane tile loops to its own longest walk, the XLA walk to
    # the batch's; the padding lanes take one step
    steps = np.asarray(got.steps)
    tiles = np.pad(steps, (0, 3), constant_values=1).reshape(-1, 8).max(1)
    assert np.array_equal(np.asarray(got.tile_steps),
                          np.repeat(tiles, 8)[:B])
    assert (np.asarray(want.tile_steps) == steps.max()).all()
    assert not np.asarray(got.path_mask)[:, :topo.n_links][:, dead].any()


@pytest.mark.parametrize("rows,cols", [(8, 8), (16, 8), (16, 5)])
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
