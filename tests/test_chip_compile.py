"""The main path's lane kernels compile for a TPU v5e, without the chip.

Interpret mode (every other kernel test) runs the kernels' math as XLA
ops on the CPU, so it cannot see what the TPU kernel compiler refuses:
primitives Mosaic has no lowering for, integer matmuls and index
reductions, block shapes it cannot lay out.  These tests compile the
kernels and both batched runners for a described v5e chip at the paper's
8x8 geometry and at a 16-channel 16x8 mesh with two dies a chip (232
links: a busy map past one 128-lane row; the walk kernel also at
16x16), with argument shapes placed on one of its devices (the runners
also sharded over all four), and check that a Pallas kernel
(``tpu_custom_call``) is in the compiled program: in the scout runner the
walk kernel.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.  JAX's persistent compilation
cache is off around these compiles, since an entry compiled for a chip
that is not attached cannot be read back here.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.core.topology import build_mesh
from repro.kernels.batched_step import B_TILE
from repro.kernels.scout_step import (STATE_W, link_pad, pack_tables,
                                      scout_walk_pallas, walk_tile)
from repro.ssd import cost_optimized, perf_optimized
from repro.ssd import sim as S
from repro.ssd.designs import lower_designs

B = 512  # two lane tiles of the static kernel's largest block
CAPACITY = S.CHUNK
# the paper's 8x8 mesh, and cost-16ch's 16x8 mesh at two dies a chip
GEOMETRIES = {
    "8x8": lambda: perf_optimized(),
    "16x8x2": lambda: cost_optimized(rows=16, cols=8, dies_per_chip=2),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _place(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows, cols, lanes, per_scout_allow", [
    (8, 8, 16, False), (8, 8, 16, True), (16, 8, 16, False),
    (16, 8, 16, True), (16, 16, B, False), (16, 16, B, True),
], ids=["8x8", "8x8-per-scout", "16x8", "16x8-per-scout", "16x16",
        "16x16-per-scout"])
def test_walk_kernel_compiles(one_chip, no_jax_cache, rows, cols, lanes,
                              per_scout_allow):
    """The walk kernel at the lane tile the shapes pick: one 16-lane tile
    for a Fig. 9 group at 8x8 and 16x8, with a compile-time and a
    per-scout ``allow_nonminimal``; at 16x16 a tile below ``B_TILE``,
    since the DFS stacks would push the full tile past ``VMEM_BUDGET``."""
    mesh = build_mesh(rows, cols)
    tables = pack_tables(mesh)
    W = link_pad(mesh.n_links)
    b_tile = walk_tile(lanes, W, mesh.n_nodes)
    assert b_tile == (B_TILE // 2 if rows == cols == 16 else lanes)
    args = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
            for s in ((lanes, STATE_W), (lanes, W), tables.shape)]
    if per_scout_allow:
        args.append(jax.ShapeDtypeStruct((lanes,), jnp.int32,
                                         sharding=one_chip))

    def walk(*a):
        return scout_walk_pallas(*a, cols=cols, n_nodes=mesh.n_nodes,
                                 interpret=False, b_tile=b_tile)

    _assert_kernel(jax.jit(walk).lower(*args).compile())


def test_walk_kernel_refuses_a_tile_past_the_budget():
    """A 256-lane tile at 16x16 outgrows ``VMEM_BUDGET`` once the stacks
    are counted: the kernel's assertion stops it before Mosaic sees it."""
    mesh = build_mesh(16, 16)
    tables = pack_tables(mesh)
    state = jnp.zeros((B_TILE, STATE_W), jnp.int32)
    busy = jnp.zeros((B_TILE, link_pad(mesh.n_links)), jnp.int32)
    with pytest.raises(AssertionError, match="outgrow VMEM"):
        scout_walk_pallas(state, busy, tables, cols=16,
                          n_nodes=mesh.n_nodes, interpret=True,
                          b_tile=B_TILE)


@pytest.mark.parametrize("variant, n_shards, geometry", [
    pytest.param("batched", 1, "8x8", id="batched"),
    pytest.param("batched", 4, "8x8", id="batched-x4"),
    pytest.param("bscout", 1, "8x8", id="bscout"),
    pytest.param("bscout", 4, "8x8", id="bscout-x4"),
    pytest.param("batched", 1, "16x8x2", id="batched-16x8x2"),
    pytest.param("bscout", 1, "16x8x2", id="bscout-16x8x2"),
])
def test_batched_runner_compiles(topo, one_chip, no_jax_cache, monkeypatch,
                                 variant, n_shards, geometry):
    """The batched static and scout runners the occupancy planner
    dispatches on an accelerator, with the compiled Pallas backend, in
    the argument layout of ``sim._avatars_for_key`` (the static runner's
    per-lane node tables included): on one chip, and sharded over the four
    chips of a v5e 2x2 (the scout runner's DFS counts, too, by lane)."""
    cfg = GEOMETRIES[geometry]()
    sig = S._geom_sig(cfg)
    per_shard = B // 2
    if variant == "batched":
        fixed = (None,) * len(S._PROMOTABLE)
        key = S.batched_group_key(sig, CAPACITY, per_shard, fixed, n_shards,
                                  "pallas")
    else:
        fixed = S._promotions(lower_designs(cfg, ("venice",)))
        key = S.bscout_group_key(sig, CAPACITY, per_shard, 1, fixed,
                                 n_shards, "pallas")
    if n_shards > 1:
        # the lane mesh over the described chips, for the avatars'
        # shardings and the runner's shard_map alike
        mesh = Mesh(np.array(topo.devices[:n_shards]), ("lanes",))
        monkeypatch.setattr(S, "_lane_mesh", lambda n: mesh)
        S._build_batched_fn.cache_clear()
        S._build_batched_scout_fn.cache_clear()
        avatars = S._avatars_for_key(key)
    else:
        avatars = _place(S._avatars_for_key(key), one_chip)
    try:
        text = S._fn_for_key(key).lower(*avatars).compile().as_text()
        assert "tpu_custom_call" in text
        if variant == "bscout":
            assert "%scout_walk" in text
    finally:
        S._build_batched_fn.cache_clear()
        S._build_batched_scout_fn.cache_clear()
