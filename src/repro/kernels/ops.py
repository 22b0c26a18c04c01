"""Jit'd wrappers and the batched-DFS driver around the scout-step kernel.

``route_batch`` routes a whole batch of scouts to their destinations by
iterating the Algorithm-1 step (Pallas kernel or jnp reference) inside a
``lax.while_loop``, with the DFS backtracking stack kept in regular JAX.
This is the building block for the design-space sweeps (§6.5) and the
beyond-paper k-scout variant (launch k candidate scouts, keep the best).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.topology import MeshTopology
from repro.kernels.backend import default_interpret
from repro.kernels.ref import scout_step_ref
from repro.kernels.scout_step import (B_TILE, STATE_W, link_pad, pack_tables,
                                      scout_step_pallas)


class BatchRouteOut(NamedTuple):
    success: jnp.ndarray  # bool [B]
    path_mask: jnp.ndarray  # bool [B, link_pad(L)]
    hops: jnp.ndarray  # int32 [B]
    steps: jnp.ndarray  # int32 [B]
    misroutes: jnp.ndarray  # int32 [B]


def _pad_b(x, b_tile):
    B = x.shape[0]
    pad = (-B) % b_tile
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], 0)
    return x


def route_dfs(step_fn, port_link, src, dst, busy0, seeds, *, n_pad, b_tile):
    """Route a batch of scouts to their destinations: the DFS driver core.

    ``step_fn(state, busy, tried) -> (state', busy', tried')`` is one
    Algorithm-1 decision step (Pallas kernel or the jnp reference); this
    function supplies the backtracking memory around it — driver-resident
    DFS stacks, push on advance, pop (and link release) on backtrack —
    inside a ``lax.while_loop``.  Plain traceable JAX: callers jit it (or
    embed it in a larger jitted program, as the batched scout lane runner
    does).

    ``busy0`` is bool/int [B, L], L the topology's link count (or a
    layout's link section); columns are padded to ``link_pad(L)``, whole
    128-lane rows, and rows to a multiple of ``b_tile`` with
    src == dst == 0 scouts that finish on the first step.
    ``n_pad`` is the packed-table row count (``pack_tables(topo).shape[0]``)
    sizing the tried bitmap.  Returned ``path_mask`` is the links this
    walk reserved (final busy minus initial busy), full padded width.
    """
    n_nodes = port_link.shape[0]
    cap = 4 * n_nodes
    B = src.shape[0]
    Bp = B + ((-B) % b_tile)
    state = jnp.zeros((Bp, STATE_W), jnp.int32)
    state = state.at[:B, 0].set(src)
    state = state.at[:B, 1].set(dst)
    state = state.at[:, 2].set(-1)
    state = state.at[:B, 3].set(seeds.astype(jnp.int32))
    busy = _pad_b(busy0.astype(jnp.int32), b_tile)
    width = link_pad(busy.shape[1])
    if busy.shape[1] < width:
        busy = jnp.pad(busy, ((0, 0), (0, width - busy.shape[1])))
    busy0_p = busy.astype(bool)
    tried = jnp.zeros((Bp, 4 * n_pad), jnp.int32)

    stack_node = jnp.zeros((Bp, cap), jnp.int32)
    stack_entry = jnp.zeros((Bp, cap), jnp.int32)
    stack_exit = jnp.zeros((Bp, cap), jnp.int32)
    stack_mis = jnp.zeros((Bp, cap), jnp.int32)
    depth = jnp.zeros((Bp,), jnp.int32)
    done = jnp.zeros((Bp,), bool)
    success = jnp.zeros((Bp,), bool)
    steps = jnp.zeros((Bp,), jnp.int32)

    def cond(c):
        return ~jnp.all(c[0])

    def body(c):
        (done, success, state, busy, tried, stack_node, stack_entry,
         stack_exit, stack_mis, depth, steps) = c
        prev_state, prev_busy = state, busy
        cur_prev = state[:, 0]
        entry_prev = state[:, 2]
        s2, b2, t2 = step_fn(state, busy, tried)
        act = ~done
        flags = s2[:, 4]
        advanced = act & (flags == 1)
        at_dst = act & (flags == 2)
        backtrack = act & (flags == 0)

        rows = jnp.arange(Bp)
        # push on advance
        d = depth
        stack_node = stack_node.at[rows, d].set(
            jnp.where(advanced, cur_prev, stack_node[rows, d])
        )
        stack_entry = stack_entry.at[rows, d].set(
            jnp.where(advanced, entry_prev, stack_entry[rows, d])
        )
        stack_exit = stack_exit.at[rows, d].set(
            jnp.where(advanced, s2[:, 5], stack_exit[rows, d])
        )
        stack_mis = stack_mis.at[rows, d].set(
            jnp.where(advanced, s2[:, 6], stack_mis[rows, d])
        )
        # pop on backtrack
        can_pop = backtrack & (depth > 0)
        fail = backtrack & (depth == 0)
        dm1 = jnp.maximum(depth - 1, 0)
        pnode = stack_node[rows, dm1]
        pentry = stack_entry[rows, dm1]
        pexit = stack_exit[rows, dm1]
        plink = port_link[pnode, pexit]
        busy_new = jnp.where(
            can_pop[:, None]
            & (jax.lax.broadcasted_iota(jnp.int32, b2.shape, 1) == plink[:, None]),
            0,
            b2,
        )
        state_new = jnp.where(act[:, None], s2, prev_state)
        state_new = state_new.at[:, 0].set(
            jnp.where(can_pop, pnode, state_new[:, 0])
        )
        state_new = state_new.at[:, 2].set(
            jnp.where(can_pop, pentry, state_new[:, 2])
        )
        busy_new = jnp.where(act[:, None], busy_new, prev_busy)
        tried_new = jnp.where(act[:, None], t2, tried)
        depth = depth + advanced.astype(jnp.int32) - can_pop.astype(jnp.int32)
        steps = steps + act.astype(jnp.int32)
        done = done | at_dst | fail
        success = success | at_dst
        return (done, success, state_new, busy_new, tried_new, stack_node,
                stack_entry, stack_exit, stack_mis, depth, steps)

    init = (done, success, state, busy, tried, stack_node, stack_entry,
            stack_exit, stack_mis, depth, steps)
    (done, success, state, busy, tried, stack_node, stack_entry,
     stack_exit, stack_mis, depth, steps) = jax.lax.while_loop(cond, body, init)

    path_mask = busy.astype(bool) & ~busy0_p
    in_path = jax.lax.broadcasted_iota(jnp.int32, stack_mis.shape, 1) < depth[:, None]
    mis = jnp.sum(stack_mis * in_path, axis=1)
    return BatchRouteOut(
        success=success[:B],
        path_mask=path_mask[:B],
        hops=depth[:B],
        steps=steps[:B],
        misroutes=mis[:B],
    )


def make_route_batch(
    topo: MeshTopology,
    use_pallas: bool = True,
    interpret: bool | None = None,
    b_tile: int = B_TILE,
    allow_nonminimal: bool = True,
    dead_links=None,
):
    """Build a jitted ``(src, dst, busy0, seeds) -> BatchRouteOut``.

    ``interpret=None`` (the default) picks interpreter mode from the
    actual JAX backend — compiled on GPU/TPU, interpreted on CPU — so
    the kernel is never silently interpreted on a real accelerator.
    Pass ``True``/``False`` to force either mode.

    ``dead_links`` (bool [n_links] or None) bakes a failed-link mask into
    the router: dead links look permanently busy to every scout — the DFS
    routes around them — and are excluded from the returned ``path_mask``
    (a scout never reserves a dead link).  None or all-False is the
    fault-free router, bit-identical to omitting the argument.
    """
    interpret = default_interpret(interpret)
    dead_row = None
    if dead_links is not None and np.any(dead_links):
        dead_row = jnp.asarray(np.asarray(dead_links, bool)[None, :],
                               jnp.int32)
    tables = jnp.asarray(pack_tables(topo))
    n_nodes = topo.n_nodes
    n_pad = tables.shape[0]
    cols = topo.cols
    port_link = jnp.asarray(topo.port_link, jnp.int32)

    if use_pallas:
        step = functools.partial(
            scout_step_pallas,
            cols=cols,
            n_nodes=n_nodes,
            allow_nonminimal=allow_nonminimal,
            interpret=interpret,
            b_tile=b_tile,
        )

        def step_fn(state, busy, tried):
            return step(state, busy, tried, tables)

    else:
        pl_, pn_ = tables[:n_nodes, 0:4], tables[:n_nodes, 4:8]

        def step_fn(state, busy, tried):
            return scout_step_ref(state, busy, tried, pl_, pn_, cols,
                                  allow_nonminimal)

    @jax.jit
    def route(src, dst, busy0, seeds):
        if dead_row is not None:
            # dead links join the global reservation state, so path_mask
            # (reserved minus initially-busy) can never include them
            busy0 = (busy0.astype(jnp.int32) | dead_row).astype(busy0.dtype)
        return route_dfs(step_fn, port_link, src, dst, busy0, seeds,
                         n_pad=n_pad, b_tile=b_tile)

    return route
