"""Gather-free lookup primitives: one-hot compare-and-reduce.

The trick behind the Pallas scout kernel (``kernels/scout_step.py``): a
per-element table lookup ``table[idx]`` over a *batch* lowers on CPU/TPU to
a generic gather — the exact lowering that made vmap-batched simulator
lanes ~50x slower in the PR-3 measurement.  Reformulated as a broadcast
compare against an iota followed by a masked reduction, the same lookup is
pure elementwise/reduce work (VPU-friendly, no scatter/gather kernels),
and it is *exact*: precisely one slot of the one-hot is set, so the integer
sum returns that slot's value bit-for-bit.

These helpers are the building blocks of the batched scout runner in
``repro.ssd.sim`` (``_make_batched_scout_step``); the Pallas kernels keep
their own column-form formulations, the layouts the TPU compiler lowers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["argmin", "onehot", "take"]


def onehot(idx, size: int):
    """bool [..., size]: slot ``idx`` set (all-false when idx out of range)."""
    return idx[..., None] == jnp.arange(size, dtype=idx.dtype)


def take(table, idx):
    """Batched ``table[b, idx[b], ...]`` without a gather.

    ``table`` [B, K, ...], ``idx`` int [B] -> [B, ...].  Integer tables
    only (the masked sum over the one-hot axis is exact because exactly
    one slot contributes).
    """
    k = table.shape[1]
    sel = onehot(idx, k).reshape(idx.shape + (k,) + (1,) * (table.ndim - 2))
    return jnp.sum(jnp.where(sel, table, 0), axis=1)


def argmin(x, keepdims: bool = False):
    """``jnp.argmin(x, axis=1)`` for an integer [B, K] array, as int32.

    Min, compare, then the min of a masked iota: the first occurrence, as
    ``jnp.argmin`` picks, without the index reduction Mosaic lowers only
    for float32.
    """
    iota = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    first = x == jnp.min(x, axis=1, keepdims=True)
    return jnp.min(jnp.where(first, iota, x.shape[1]), axis=1,
                   keepdims=keepdims)
