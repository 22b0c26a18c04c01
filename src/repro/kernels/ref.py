"""Pure-jnp oracle for the batched scout decision step (``step_math``).

Deliberately written WITHOUT the kernel's one-hot-matmul tricks: plain
``take``/indexing gathers, so a bug in the kernel's TPU-native formulation
cannot hide in a shared implementation.  Decision semantics (candidate
ordering, xorshift32 tie-break, unsigned modulo) mirror
``repro.core.routing.scout_route_ref``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.scout_step import umod, xorshift32_i32

RIGHT, UP, LEFT, DOWN = 0, 1, 2, 3


def scout_step_ref(state, busy, tried, port_link, port_neighbor, cols,
                   allow_nonminimal=True):
    """Reference step: same signature semantics as ``step_math`` but with
    gather-based lookups. state [B,8]; busy [B,L] 0/1; tried [B,4N] 0/1."""
    cur, dst, entry, rng = state[:, 0], state[:, 1], state[:, 2], state[:, 3]
    links4 = port_link[cur]  # [B, 4] gather
    nbrs4 = port_neighbor[cur]

    busyb = busy.astype(bool)
    triedb = tried.astype(bool)
    B = cur.shape[0]
    rows = jnp.arange(B)
    busy4 = busyb[rows[:, None], jnp.clip(links4, 0, busy.shape[1] - 1)]
    tried4 = triedb[rows[:, None], cur[:, None] * 4 + jnp.arange(4)[None, :]]
    free4 = (links4 >= 0) & ~busy4 & ~tried4

    at_dst = cur == dst
    diffx = dst % cols - cur % cols
    diffy = dst // cols - cur // cols
    px = jnp.where(diffx > 0, RIGHT, jnp.where(diffx < 0, LEFT, -1))
    py = jnp.where(diffy > 0, UP, jnp.where(diffy < 0, DOWN, -1))

    def port_free(p):
        return (p >= 0) & free4[rows, jnp.clip(p, 0, 3)]

    fmin = jnp.stack([port_free(px), port_free(py)], axis=1)
    n_min = fmin.sum(1)
    iota4 = jnp.arange(4)[None, :]
    fmis = free4 & (iota4 != entry[:, None])
    if not allow_nonminimal:
        fmis = jnp.zeros_like(fmis)
    n_mis = fmis.sum(1)

    use_min = n_min > 0
    count = jnp.where(use_min, n_min, n_mis).astype(jnp.int32)
    need_rng = (~at_dst) & (count > 1)
    rng_next = jnp.where(need_rng, xorshift32_i32(rng), rng)
    idx = umod(rng_next, jnp.maximum(count, 1))

    cand_ports = jnp.concatenate(
        [px[:, None], py[:, None], jnp.broadcast_to(iota4, (B, 4))], axis=1
    )
    cand_flags = jnp.concatenate(
        [fmin & use_min[:, None], fmis & ~use_min[:, None]], axis=1
    )
    cum = jnp.cumsum(cand_flags, axis=1)
    sel = cand_flags & (cum - 1 == idx[:, None])
    pick = jnp.sum(jnp.where(sel, cand_ports, 0), axis=1).astype(jnp.int32)
    has_pick = (count > 0) & ~at_dst

    link_pick = links4[rows, jnp.clip(pick, 0, 3)]
    nbr_pick = nbrs4[rows, jnp.clip(pick, 0, 3)]
    new_cur = jnp.where(has_pick, nbr_pick, cur)
    new_entry = jnp.where(has_pick, (pick + 2) % 4, entry)
    flags = jnp.where(at_dst, 2, jnp.where(has_pick, 1, 0)).astype(jnp.int32)
    out_pick = jnp.where(has_pick, pick, -1)
    is_mis = (has_pick & ~use_min).astype(jnp.int32)

    state_out = jnp.stack(
        [new_cur, dst, new_entry, rng_next, flags, out_pick, is_mis,
         jnp.where(has_pick, link_pick, 0)],
        axis=1,
    )
    busy_out = busyb.at[rows, jnp.clip(link_pick, 0, busy.shape[1] - 1)].set(
        busyb[rows, jnp.clip(link_pick, 0, busy.shape[1] - 1)] | has_pick
    )
    tried_out = triedb.at[rows, cur * 4 + jnp.clip(pick, 0, 3)].set(
        triedb[rows, cur * 4 + jnp.clip(pick, 0, 3)] | has_pick
    )
    return state_out, busy_out.astype(jnp.int32), tried_out.astype(jnp.int32)
