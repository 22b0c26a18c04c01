"""Lane-tiled Pallas wrapper for the batched static step.

The batched runner in ``ssd.sim`` scans a per-tick step function
``step(sp, state, xs) -> (state', out)`` over time-major transaction
tables, where every pytree leaf carries the lane batch ``B`` as its
leading axis and all math is per-lane (element-wise plus reductions over
trailing axes only — one-hot lookups and shift-unpacked mask words
replace every gather).  That shape is exactly a Pallas
grid program: tile the lane axis over the grid, hand each program
instance a ``(b_tile, ...)`` block of every operand (scalars, carried
state, and one tick of the bit-packed node tables, which the batched run
gathers per chunk, outside this kernel, from each lane's
``designs.node_tables``), and run the *same* step closure on the block.

``lane_tiled_step`` is deliberately generic: it takes the step function
built by ``sim._make_batched_static_step`` (or any step with the same
contract) and returns a drop-in replacement whose body is a
``pl.pallas_call``.  Because the kernel body *is* the original step —
flatten, block, unflatten, call — bit-exactness against the XLA path is
by construction, not by re-implementation; the parity tests pin it
anyway.  Invalid steps stay no-ops for free: the masked-arithmetic
validity path (``enable`` lanes, ``where``-substituted outputs) rides
along inside the step closure untouched.

On CPU the wrapper runs in interpreter mode (Pallas has no CPU
compiler); the kernel body is traced into the surrounding jitted scan,
so CI exercises the identical program structure without an accelerator.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import default_interpret

# Largest lane tile.  The step math is purely per-lane, so any tiling of
# the batch axis is value-identical; 256 lanes keeps the per-instance
# working set (state + one tick of tables) comfortably inside VMEM-scale
# scratch for every geometry in the registry.
B_TILE = 256


def lane_tile(B: int) -> int:
    """The largest power-of-two lane tile of at most ``B_TILE`` and at
    least 8 rows (the TPU sublane count) that divides ``B``; ``B`` itself
    — a grid of 1, still a valid (and bit-exact) layout — when none does.
    The occupancy planner pads its batches to a multiple of 8."""
    bt = B_TILE
    while bt >= 8:
        if B % bt == 0:
            return bt
        bt //= 2
    return B


def _pick_tile(B: int, b_tile: int | None) -> int:
    if b_tile is None:
        return lane_tile(B)
    if b_tile > 0 and B % b_tile == 0:
        return b_tile
    return B


def lanes_2d(tree):
    """Lane-major 2D int32 view of a pytree whose leaves lead with the
    lane axis: ``[B, ...] -> [B, prod(...)]`` (``[B] -> [B, 1]``), bools
    as 0/1.  Per-lane scalars become columns and no block has rank 1 or
    3+ — the layouts the TPU kernel compiler lowers."""
    return jax.tree_util.tree_map(
        lambda a: a.reshape(a.shape[0], -1).astype(jnp.int32), tree)


def lane_tiled_step(step_fn, *, b_tile: int | None = None,
                    interpret: bool | None = None):
    """Wrap ``step_fn(sp, state, xs) -> (state', out)`` in a lane-tiled
    ``pl.pallas_call``.

    Every leaf of ``(sp, state, xs)`` and of the result must carry the
    lane batch as its leading axis; for the TPU compiler, in the
    :func:`lanes_2d` form.  ``interpret=None`` resolves via
    :func:`repro.kernels.backend.default_interpret`.
    """
    interp = default_interpret(interpret)

    def call(sp, state, xs):
        in_leaves, in_tree = jax.tree_util.tree_flatten((sp, state, xs))
        B = in_leaves[0].shape[0]
        bt = _pick_tile(B, b_tile)
        out_avatars = jax.eval_shape(step_fn, sp, state, xs)
        out_leaves, out_tree = jax.tree_util.tree_flatten(out_avatars)
        n_in = len(in_leaves)

        def kernel(*refs):
            vals = [r[...] for r in refs[:n_in]]
            sp_b, state_b, xs_b = jax.tree_util.tree_unflatten(in_tree, vals)
            new_state, out = step_fn(sp_b, state_b, xs_b)
            res = jax.tree_util.tree_leaves((new_state, out))
            for r, v in zip(refs[n_in:], res):
                r[...] = v.astype(r.dtype)

        def spec(leaf):
            nd = leaf.ndim
            return pl.BlockSpec(
                (bt,) + tuple(leaf.shape[1:]),
                lambda i, _nd=nd: (i,) + (0,) * (_nd - 1),
            )

        outs = pl.pallas_call(
            kernel,
            grid=(B // bt,),
            in_specs=[spec(l) for l in in_leaves],
            out_specs=[spec(l) for l in out_leaves],
            out_shape=[jax.ShapeDtypeStruct(l.shape, l.dtype)
                       for l in out_leaves],
            interpret=interp,
        )(*in_leaves)
        return jax.tree_util.tree_unflatten(out_tree, list(outs))

    return call
