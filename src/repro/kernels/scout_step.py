"""Pallas TPU kernel: batched Algorithm-1 scout routing step.

The paper's perf-critical compute is stepping many scout state machines
against the link-occupancy map (§4.3: every in-flight I/O request runs the
routing algorithm, and the design-space sweeps in §6.5 step millions of
scouts).  A GPU port would chase pointers per packet; the TPU-native
formulation instead makes every per-node table lookup a *compare-and-reduce
against broadcast iotas* over the whole scout batch — pure VPU/MXU work with
no gathers:

  * ``port_link[cur, p]`` becomes ``one_hot(cur) · port_link`` (a [B,N]×[N,4]
    matmul on the MXU),
  * per-port busy/tried tests become ``(ids[...,None] == iota) & bitmap``
    reductions over the lane dimension.

Layout: scout state is packed into an int32 ``[B, 8]`` array (cur, dst,
entry, rng, 4 pad lanes); busy is ``[B, 128]`` (112 mesh links + pad) and
tried is ``[B, 256]`` (64 nodes x 4 ports).  The batch is tiled over the grid
with explicit VMEM BlockSpecs; one tile's working set at B_TILE=256 is
256x(8+128+256+128+8)x4B ≈ 541 KiB < 1 MiB VMEM in fp32 words — comfortably
resident, with the lane dimension 128-aligned for the VPU.

The kernel computes the *decision* of Algorithm 1 (minimal-adaptive with
random tie-break, else misroute, else backtrack) plus the state advance;
the DFS stack (backtracking memory) lives in the driver (``ops.py``), which
is regular JAX.  ``ref.py`` is the pure-jnp oracle; tests sweep shapes,
meshes and occupancy densities in ``interpret=True`` mode and also replay
full DFS walks against ``repro.core.routing.scout_route_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.topology import MeshTopology
from repro.kernels.backend import default_interpret

RIGHT, UP, LEFT, DOWN = 0, 1, 2, 3
LINK_PAD = 128  # lane-aligned link bitmap (8x8 mesh has 112 links)
STATE_W = 8  # cur, dst, entry, rng, flags(out), pick(out), pad, pad
B_TILE = 256


def umod(x, m):
    """Unsigned mod of the int32 bit-pattern ``x`` by ``m`` (element-wise).

    x_u = hi·2^31 + lo with hi = logical msb, lo = low 31 bits, so
    x_u mod m = (lo mod m + hi·(2^31 mod m)) mod m — all in int32.
    """
    hi = jax.lax.shift_right_logical(x, 31)
    lo = x & jnp.int32(0x7FFFFFFF)
    c = (jnp.int32(2**30) % m) * 2 % m  # 2^31 mod m without overflow
    return (lo % m + hi * c) % m


def xorshift32_i32(x):
    """xorshift32 on int32 bit patterns (logical right shifts)."""
    x = x ^ (x << 13)
    x = x ^ jax.lax.shift_right_logical(x, 17)
    x = x ^ (x << 5)
    return x


def step_math(state, busy, tried, port_link, port_neighbor, cols, allow_nonminimal):
    """Algorithm-1 decision + state advance for a batch of scouts.

    Shared by the Pallas kernel body and the jnp reference — the kernel's
    value is the *layout/tiling*; the math must be identical by construction.
    All inputs are int32/bool jnp arrays:
      state [B, 8], busy [B, L], tried [B, 4N],
      port_link [N, 4], port_neighbor [N, 4].
    ``allow_nonminimal`` may be a static bool or a per-scout bool vector
    [B] (the table-driven design sweep batches scouts whose routing mode
    differs).  Degenerate/padded scouts are fine: ``cur == dst`` finishes
    immediately and off-mesh ports (link id -1) are never free.
    Returns (state', busy', tried').
    """
    # per-scout scalars stay [B, 1] columns and every bool is combined
    # with select, never cast or stacked: the layouts Mosaic lowers
    cur = state[:, 0:1]
    dst = state[:, 1:2]
    entry = state[:, 2:3]
    rng = state[:, 3:4]
    B = state.shape[0]
    n_nodes = port_link.shape[0]
    iota_n = jax.lax.broadcasted_iota(jnp.int32, (B, n_nodes), 1)
    one_hot_cur = jnp.where(iota_n == cur, 1.0, 0.0).astype(jnp.float32)

    def mxu_take(table):
        # the MXU has no int32 matmul; a one-hot f32 product at full
        # precision is exact for table ids (|id| < 2^24)
        return jax.lax.dot(one_hot_cur, table.astype(jnp.float32),
                           precision=jax.lax.Precision.HIGHEST
                           ).astype(jnp.int32)

    # MXU gathers: per-port link ids / neighbor ids for each scout's node
    links4 = mxu_take(port_link)  # [B, 4]
    nbrs4 = mxu_take(port_neighbor)
    link_p = [links4[:, p:p + 1] for p in range(4)]
    nbr_p = [nbrs4[:, p:p + 1] for p in range(4)]

    iota_l = jax.lax.broadcasted_iota(jnp.int32, busy.shape, 1)
    iota_t = jax.lax.broadcasted_iota(jnp.int32, tried.shape, 1)

    def bit(bitmap, iota, col):
        """bitmap[b, col[b]] != 0 (False for an out-of-range col)."""
        hit = jnp.where((iota == col) & (bitmap != 0), 1, 0)
        return jnp.max(hit, axis=1, keepdims=True) > 0

    # per port: a link exists, is not busy, and was not tried from here
    free = [(link_p[p] >= 0) & ~bit(busy, iota_l, link_p[p])
            & ~bit(tried, iota_t, cur * 4 + p) for p in range(4)]

    at_dst = cur == dst
    diffx = dst % cols - cur % cols
    diffy = dst // cols - cur // cols
    px = jnp.where(diffx > 0, RIGHT, jnp.where(diffx < 0, LEFT, -1))
    py = jnp.where(diffy > 0, UP, jnp.where(diffy < 0, DOWN, -1))

    def any_port(d):
        out = (d == 0) & free[0]
        for p in range(1, 4):
            out = out | ((d == p) & free[p])
        return out

    def count(flags):
        n = jnp.zeros_like(cur)
        for f in flags:
            n = n + jnp.where(f, 1, 0)
        return n

    fmin_x, fmin_y = any_port(px), any_port(py)
    n_min = count((fmin_x, fmin_y))
    # scalar or per-scout [B] flag
    allow = jnp.asarray(allow_nonminimal).reshape(-1, 1)
    fmis = [free[p] & (entry != p) & allow for p in range(4)]
    n_mis = count(fmis)

    use_min = n_min > 0
    n_cand = jnp.where(use_min, n_min, n_mis)
    need_rng = (~at_dst) & (n_cand > 1)
    rng_next = jnp.where(need_rng, xorshift32_i32(rng), rng)
    idx = umod(rng_next, jnp.maximum(n_cand, 1))

    # the idx-th set flag of the 6 candidates [px, py, port 0..3], as an
    # explicit running count over the columns (Mosaic has no cumsum)
    cand = [(fmin_x & use_min, px), (fmin_y & use_min, py)] + [
        (fmis[p] & ~use_min, p) for p in range(4)
    ]
    run = jnp.zeros_like(idx)
    pick = jnp.zeros_like(idx)
    for flag, port in cand:
        run = run + jnp.where(flag, 1, 0)
        pick = pick + jnp.where(flag & (run - 1 == idx), port, 0)
    has_pick = (n_cand > 0) & ~at_dst

    # advance
    link_pick = jnp.zeros_like(pick)
    nbr_pick = jnp.zeros_like(pick)
    for p in range(4):
        link_pick = link_pick + jnp.where(pick == p, link_p[p], 0)
        nbr_pick = nbr_pick + jnp.where(pick == p, nbr_p[p], 0)
    opposite = (pick + 2) % 4

    new_cur = jnp.where(has_pick, nbr_pick, cur)
    new_entry = jnp.where(has_pick, opposite, entry)
    # flags: 0 = backtrack, 1 = advanced, 2 = at destination
    flags = jnp.where(at_dst, 2, jnp.where(has_pick, 1, 0))
    out_pick = jnp.where(has_pick, pick, -1)
    is_mis = jnp.where(has_pick & ~use_min, 1, 0)

    out_cols = (new_cur, dst, new_entry, rng_next, flags, out_pick, is_mis,
                jnp.where(has_pick, link_pick, 0))
    iota_s = jax.lax.broadcasted_iota(jnp.int32, (B, STATE_W), 1)
    state_out = jnp.zeros((B, STATE_W), jnp.int32)
    for k, col in enumerate(out_cols):
        state_out = jnp.where(iota_s == k, col, state_out)
    # set busy/tried bits for the traversed port
    busy_out = jnp.where(
        (busy != 0) | (has_pick & (iota_l == link_pick)), 1, 0)
    tried_out = jnp.where(
        (tried != 0) | (has_pick & (iota_t == cur * 4 + pick)), 1, 0)
    return state_out, busy_out, tried_out


def _kernel(state_ref, busy_ref, tried_ref, tables_ref, state_o, busy_o, tried_o,
            *, cols, n_nodes, allow_nonminimal):
    state = state_ref[...]
    busy = busy_ref[...]
    tried = tried_ref[...]
    tables = tables_ref[...]  # [N_pad, 128]: cols 0-3 port_link, 4-7 neighbor
    port_link = tables[:n_nodes, 0:4]
    port_neighbor = tables[:n_nodes, 4:8]
    s, b, t = step_math(
        state, busy, tried, port_link, port_neighbor, cols, allow_nonminimal
    )
    state_o[...] = s
    busy_o[...] = b
    tried_o[...] = t


def _kernel_vec(state_ref, busy_ref, tried_ref, tables_ref, allow_ref,
                state_o, busy_o, tried_o, *, cols, n_nodes):
    """Per-scout ``allow_nonminimal`` variant: the flag rides in as a
    traced ``[B, 1]`` int32 operand instead of a compile-time constant —
    one executable serves pools that mix minimal-only and adaptive
    scouts (the batched scout lane runner batches across designs)."""
    state = state_ref[...]
    busy = busy_ref[...]
    tried = tried_ref[...]
    tables = tables_ref[...]
    allow = allow_ref[...] != 0
    port_link = tables[:n_nodes, 0:4]
    port_neighbor = tables[:n_nodes, 4:8]
    s, b, t = step_math(
        state, busy, tried, port_link, port_neighbor, cols, allow
    )
    state_o[...] = s
    busy_o[...] = b
    tried_o[...] = t


def pack_tables(topo: MeshTopology) -> np.ndarray:
    n_pad = -(-topo.n_nodes // 8) * 8
    t = np.full((n_pad, 128), -1, dtype=np.int32)
    t[: topo.n_nodes, 0:4] = topo.port_link
    t[: topo.n_nodes, 4:8] = topo.port_neighbor
    return t


@functools.partial(
    jax.jit,
    static_argnames=("cols", "n_nodes", "allow_nonminimal", "interpret", "b_tile"),
)
def scout_step_pallas(
    state,
    busy,
    tried,
    tables,
    allow_vec=None,
    *,
    cols: int,
    n_nodes: int,
    allow_nonminimal: bool = True,
    interpret: bool | None = None,
    b_tile: int = B_TILE,
):
    """Run one Algorithm-1 step for a batch of scouts via pallas_call.

    state [B, 8] int32; busy [B, LINK_PAD] int32 (0/1); tried [B, 4*N_pad]
    int32 (0/1); tables from ``pack_tables``.  B must be a multiple of
    ``b_tile`` (pad with dummy scouts).  ``interpret=None`` resolves from
    the actual JAX backend (compiled on GPU/TPU, interpreted on CPU).

    ``allow_vec`` (int32/bool [B] or [B, 1], traced) carries a per-scout
    ``allow_nonminimal`` flag for pools that mix routing modes; when given
    it supersedes the static ``allow_nonminimal`` constant (which stays
    the cheaper choice for uniform pools — no extra operand to stream).
    """
    interpret = default_interpret(interpret)
    B = state.shape[0]
    assert B % b_tile == 0, "pad the scout batch to a multiple of b_tile"
    T = tried.shape[1]
    grid = (B // b_tile,)
    in_specs = [
        pl.BlockSpec((b_tile, STATE_W), lambda i: (i, 0)),
        pl.BlockSpec((b_tile, busy.shape[1]), lambda i: (i, 0)),
        pl.BlockSpec((b_tile, T), lambda i: (i, 0)),
        pl.BlockSpec((tables.shape[0], 128), lambda i: (0, 0)),
    ]
    if allow_vec is None:
        kernel = functools.partial(
            _kernel, cols=cols, n_nodes=n_nodes,
            allow_nonminimal=allow_nonminimal,
        )
        operands = (state, busy, tried, tables)
    else:
        kernel = functools.partial(_kernel_vec, cols=cols, n_nodes=n_nodes)
        in_specs.append(pl.BlockSpec((b_tile, 1), lambda i: (i, 0)))
        operands = (state, busy, tried, tables,
                    allow_vec.astype(jnp.int32).reshape(B, 1))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((b_tile, STATE_W), lambda i: (i, 0)),
            pl.BlockSpec((b_tile, busy.shape[1]), lambda i: (i, 0)),
            pl.BlockSpec((b_tile, T), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, STATE_W), jnp.int32),
            jax.ShapeDtypeStruct((B, busy.shape[1]), jnp.int32),
            jax.ShapeDtypeStruct((B, T), jnp.int32),
        ],
        interpret=interpret,
        name="scout_step_pallas",
    )(*operands)
