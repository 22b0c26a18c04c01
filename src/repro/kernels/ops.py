"""Jit'd wrappers around the scout-walk kernel and its XLA twin.

``route_dfs`` routes a whole batch of scouts to their destinations, one
DFS walk each: ``scout_step.walk_lanes`` (the Algorithm-1 step, its
backtracking stacks and the loop until every scout is done) runs inside
one Pallas call per lane tile on the chip (``scout_walk_pallas``), and as
XLA ops off it.  This is the building block for the design-space sweeps
(§6.5) and the beyond-paper k-scout variant (launch k candidate scouts,
keep the best).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.topology import MeshTopology
from repro.kernels.backend import default_interpret
from repro.kernels.scout_step import (STATE_W, link_pad, pack_tables,
                                      scout_walk_pallas, walk_lanes,
                                      walk_tile)


class BatchRouteOut(NamedTuple):
    success: jnp.ndarray  # bool [B]
    path_mask: jnp.ndarray  # bool [B, link_pad(L)]
    hops: jnp.ndarray  # int32 [B]
    steps: jnp.ndarray  # int32 [B]
    misroutes: jnp.ndarray  # int32 [B]
    # int32 [B]: the DFS loop's iterations in the lane's tile, its longest
    # walk (per lane tile of the walk kernel; of the whole batch as XLA ops)
    tile_steps: jnp.ndarray


def _pad_b(x, b_tile):
    B = x.shape[0]
    pad = (-B) % b_tile
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], 0)
    return x


def route_dfs(tables, src, dst, busy0, seeds, allow_nonminimal=True, *,
              cols, n_nodes, use_pallas, interpret=None):
    """Route a batch of scouts to their destinations: one DFS walk each.

    ``tables`` is ``pack_tables(topo)``; ``busy0`` is bool/int [B, L], L
    the topology's link count (or a layout's link section), its columns
    padded to ``link_pad(L)``, whole 128-lane rows.  ``allow_nonminimal``
    is a static bool or a traced per-scout [B] flag.  ``use_pallas`` runs
    the walk kernel, one ``pallas_call`` per lane tile of
    ``walk_tile``, rows padded to a multiple of it with src == dst == 0
    scouts that finish on the first step; else ``walk_lanes`` runs as XLA
    ops over the whole batch.  Both give the same walks, bit for bit;
    ``tile_steps`` counts the loop of each lane's own tile.
    Plain traceable JAX: callers jit it (or embed it in a larger jitted
    program, as the batched scout lane runner does).  Returned
    ``path_mask`` is the links this walk reserved (final busy minus
    initial busy), full padded width.
    """
    B = src.shape[0]
    busy = busy0.astype(jnp.int32)
    width = link_pad(busy.shape[1])
    busy = jnp.pad(busy, ((0, 0), (0, width - busy.shape[1])))
    b_tile = walk_tile(B, width, n_nodes) if use_pallas else B
    Bp = B + ((-B) % b_tile)
    state = jnp.zeros((Bp, STATE_W), jnp.int32)
    state = state.at[:B, 0].set(src)
    state = state.at[:B, 1].set(dst)
    state = state.at[:, 2].set(-1)
    state = state.at[:B, 3].set(seeds.astype(jnp.int32))
    busy = _pad_b(busy, b_tile)
    if not use_pallas:
        res, path = walk_lanes(state, busy, tables[:n_nodes, 0:4],
                               tables[:n_nodes, 4:8], cols, allow_nonminimal)
    elif isinstance(allow_nonminimal, (bool, np.bool_)):
        res, path = scout_walk_pallas(
            state, busy, tables, cols=cols, n_nodes=n_nodes,
            allow_nonminimal=bool(allow_nonminimal), interpret=interpret,
            b_tile=b_tile)
    else:
        res, path = scout_walk_pallas(
            state, busy, tables, _pad_b(jnp.asarray(allow_nonminimal), b_tile),
            cols=cols, n_nodes=n_nodes, interpret=interpret, b_tile=b_tile)
    return BatchRouteOut(
        success=res[:B, 0] != 0,
        path_mask=path[:B] != 0,
        hops=res[:B, 1],
        steps=res[:B, 2],
        misroutes=res[:B, 3],
        tile_steps=res[:B, 4],
    )


def make_route_batch(
    topo: MeshTopology,
    use_pallas: bool = True,
    interpret: bool | None = None,
    allow_nonminimal: bool = True,
    dead_links=None,
):
    """Build a jitted ``(src, dst, busy0, seeds) -> BatchRouteOut``.

    ``use_pallas`` walks in the Pallas walk kernel, else as XLA ops.
    ``interpret=None`` (the default) picks interpreter mode from
    the actual JAX backend — compiled on GPU/TPU, interpreted on CPU — so
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

    @jax.jit
    def route(src, dst, busy0, seeds):
        if dead_row is not None:
            # dead links join the global reservation state, so path_mask
            # (reserved minus initially-busy) can never include them
            busy0 = (busy0.astype(jnp.int32) | dead_row).astype(busy0.dtype)
        return route_dfs(tables, src, dst, busy0, seeds, allow_nonminimal,
                         cols=topo.cols, n_nodes=topo.n_nodes,
                         use_pallas=use_pallas, interpret=interpret)

    return route
