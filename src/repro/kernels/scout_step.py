"""Pallas TPU kernel: batched Algorithm-1 scout routing, one whole DFS
walk per call.

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
entry, rng, 4 pad lanes); busy is ``[B, link_pad(n_links)]``, the mesh's
link count rounded up to whole 128-lane vreg rows (128 for the 8x8 mesh's
112 links, 256 for the 16x8 mesh's 232), and tried is ``[B, 4 * N_pad]``
(4 ports per node; 256 on 8x8, 512 on 16x8).  The batch is tiled over the
grid with explicit VMEM BlockSpecs: a grid step's blocks, the DFS stacks
included, must fit ``VMEM_BUDGET`` (``block_bytes``).

``step_math`` is the *decision* of Algorithm 1 (minimal-adaptive with
random tie-break, else misroute, else backtrack) plus the state advance.
``walk_lanes`` is the DFS around it: the backtracking stacks (node, entry
port, reserved link and misroute flag of every hop), push on advance, pop
and link release on backtrack, all as selects against broadcast iotas, in
a ``while_loop`` until every scout of the batch has arrived or failed.
``scout_walk_pallas`` runs that whole walk inside one ``pallas_call`` per
lane tile, so state, busy map, tried bitmap and stacks stay in VMEM from
the first step to the last; off the chip ``ops.route_dfs`` calls
``walk_lanes`` directly, as XLA ops.  Tests hold ``step_math`` to
``ref.py``, the pure-jnp oracle, over shapes, meshes and occupancy
densities, the walk kernel (``interpret=True``) to the XLA walk, and
replay full DFS walks against ``repro.core.routing.scout_route_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.topology import MeshTopology
from repro.kernels.backend import default_interpret
from repro.kernels.batched_step import lane_tile

RIGHT, UP, LEFT, DOWN = 0, 1, 2, 3
LANES = 128  # TPU vreg lane width: a row of int32 words pads to it in VMEM
STATE_W = 8  # cur, dst, entry, rng, flags(out), pick(out), pad, pad
# VMEM a grid step's blocks may take: half of the 16 MiB of scoped VMEM
# Mosaic gives a kernel on a v5e, the rest left to its temporaries
VMEM_BUDGET = 8 << 20


def link_pad(n_links: int) -> int:
    """Columns of the busy map: ``n_links`` rounded up to whole lane rows."""
    return max(1, -(-n_links // LANES)) * LANES


def block_bytes(b_tile: int, link_w: int, n_nodes: int) -> int:
    """VMEM of one walk-kernel grid step at lane tile ``b_tile``: the
    state, busy and tried blocks, in and out, double-buffered, the four
    DFS stacks and the row of per-lane counters.  The tried bitmap and
    each stack are ``link_pad(4 * n_nodes)`` words, every row lane-padded."""
    width = link_pad(4 * n_nodes)
    words = (4 * (link_pad(STATE_W) + link_w + width) + 4 * width
             + link_pad(STATE_W))
    return 4 * b_tile * words


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


def walk_lanes(state, busy0, port_link, port_neighbor, cols, allow_nonminimal):
    """Walk every scout of the batch to its destination or to failure.

    The DFS loop around :func:`step_math`: one decision step, then the
    backtracking memory, until every scout is done.  ``state`` [B, 8]
    holds each scout's src (as cur), dst, entry -1 and rng seed; ``busy0``
    [B, L] int32 0/1 is its occupancy map.  Four stacks of
    ``link_pad(4 * N)`` words hold each hop's node, entry port, reserved
    link and misroute flag; a push, a pop and the link release are selects
    against broadcast iotas, never scatters or gathers, so the same code
    runs as XLA ops and inside a Pallas kernel.  A done scout is frozen:
    its state, maps and counters no longer change.

    Returns ``(res, path)``: res [B, 8] int32 with columns success, hops,
    steps (decision steps taken while active), misroutes and the loop's
    iterations (the batch's longest walk, on every lane); path [B, L]
    int32, the links the walk reserved (final busy minus ``busy0``).
    """
    B = state.shape[0]
    width = link_pad(4 * port_link.shape[0])
    iota_w = jax.lax.broadcasted_iota(jnp.int32, (B, width), 1)
    iota_l = jax.lax.broadcasted_iota(jnp.int32, busy0.shape, 1)
    iota_s = jax.lax.broadcasted_iota(jnp.int32, (B, STATE_W), 1)

    def one(flag):
        return jnp.where(flag, 1, 0)

    def columns(*values):
        # per-lane counters ride in one [B, 8] array: a carried [B, 1]
        # column is a layout Mosaic cannot keep across loop iterations
        out = jnp.zeros((B, STATE_W), jnp.int32)
        for k, col in enumerate(values):
            out = jnp.where(iota_s == k, col, out)
        return out

    def cond(c):
        return jnp.min(c[-1][:, 3:4]) == 0  # some scout is not done

    def body(c):
        state, busy, tried, s_node, s_entry, s_link, s_mis, ctl = c
        depth, steps, success, done = (ctl[:, k:k + 1] for k in range(4))
        s2, b2, t2 = step_math(state, busy, tried, port_link, port_neighbor,
                               cols, allow_nonminimal)
        act = done == 0
        flags = s2[:, 4:5]
        advanced = act & (flags == 1)
        at_dst = act & (flags == 2)
        backtrack = act & (flags == 0)
        can_pop = backtrack & (depth > 0)
        fail = backtrack & (depth == 0)
        # push the hop just taken at ``depth``
        push = advanced & (iota_w == depth)
        s_node = jnp.where(push, state[:, 0:1], s_node)
        s_entry = jnp.where(push, state[:, 2:3], s_entry)
        s_link = jnp.where(push, s2[:, 7:8], s_link)
        s_mis = jnp.where(push, s2[:, 6:7], s_mis)
        # pop the top hop: back to its node and entry, its link released
        top = iota_w == depth - 1

        def pop(stack):
            return jnp.max(jnp.where(top, stack, jnp.iinfo(jnp.int32).min),
                           axis=1, keepdims=True)

        pnode, pentry, plink = pop(s_node), pop(s_entry), pop(s_link)
        b2 = jnp.where(can_pop & (iota_l == plink), 0, b2)
        s2 = jnp.where(can_pop & (iota_s == 0), pnode, s2)
        s2 = jnp.where(can_pop & (iota_s == 2), pentry, s2)
        ctl = columns(depth + one(advanced) - one(can_pop), steps + one(act),
                      jnp.where(at_dst, 1, success),
                      jnp.where(at_dst | fail, 1, done))
        return (jnp.where(act, s2, state), jnp.where(act, b2, busy),
                jnp.where(act, t2, tried), s_node, s_entry, s_link, s_mis, ctl)

    zeros = jnp.zeros((B, width), jnp.int32)
    init = (state, busy0) + (zeros,) * 5 + (jnp.zeros((B, STATE_W), jnp.int32),)
    _, busy, _, _, _, _, s_mis, ctl = jax.lax.while_loop(cond, body, init)
    depth, steps, success = (ctl[:, k:k + 1] for k in range(3))
    mis = jnp.sum(jnp.where(iota_w < depth, s_mis, 0), axis=1, keepdims=True)
    # every scout steps until done, so the loop ran its longest walk
    return (columns(success, depth, steps, mis, jnp.max(steps)),
            one((busy != 0) & (busy0 == 0)))


def pack_tables(topo: MeshTopology) -> np.ndarray:
    n_pad = -(-topo.n_nodes // 8) * 8
    t = np.full((n_pad, 128), -1, dtype=np.int32)
    t[: topo.n_nodes, 0:4] = topo.port_link
    t[: topo.n_nodes, 4:8] = topo.port_neighbor
    return t


def walk_tile(B: int, link_w: int, n_nodes: int) -> int:
    """The walk kernel's lane tile for a batch of ``B`` scouts: the largest
    :func:`~repro.kernels.batched_step.lane_tile` of ``B`` rounded up to
    whole sublanes whose blocks, stacks included, fit ``VMEM_BUDGET``."""
    bt = lane_tile(-(-B // 8) * 8)
    while bt > 8 and block_bytes(bt, link_w, n_nodes) > VMEM_BUDGET:
        bt //= 2
    return bt


def _walk_kernel(state_ref, busy_ref, tables_ref, *refs, cols, n_nodes,
                 allow_nonminimal):
    if allow_nonminimal is None:  # per-scout flag, a [b_tile, 1] block
        allow_ref, res_o, path_o = refs
        allow = allow_ref[...] != 0
    else:
        res_o, path_o = refs
        allow = allow_nonminimal
    tables = tables_ref[...]
    res, path = walk_lanes(state_ref[...], busy_ref[...],
                           tables[:n_nodes, 0:4], tables[:n_nodes, 4:8],
                           cols, allow)
    res_o[...] = res
    path_o[...] = path


@functools.partial(
    jax.jit,
    static_argnames=("cols", "n_nodes", "allow_nonminimal", "interpret",
                     "b_tile"),
)
def scout_walk_pallas(
    state,
    busy,
    tables,
    allow_vec=None,
    *,
    cols: int,
    n_nodes: int,
    allow_nonminimal: bool = True,
    interpret: bool | None = None,
    b_tile: int,
):
    """Walk a batch of scouts to their destinations, one ``pallas_call``
    per lane tile: :func:`walk_lanes` inside the kernel.

    state [B, 8] int32 (src as cur, dst, entry -1, rng seed); busy
    [B, link_pad(n_links)] int32 0/1; tables from ``pack_tables``.  B must
    be a multiple of ``b_tile`` (:func:`walk_tile`), whose blocks fit
    ``VMEM_BUDGET``.  ``allow_vec`` (int32/bool [B], traced) carries a
    per-scout ``allow_nonminimal`` flag and then supersedes the static one.
    Returns ``walk_lanes``'s ``(res [B, 8], path [B, link_pad(n_links)])``,
    each tile walked apart: its loop-iterations column is its own tile's.
    """
    interpret = default_interpret(interpret)
    B, W = busy.shape
    assert B % b_tile == 0, "pad the scout batch to a multiple of b_tile"
    assert block_bytes(b_tile, W, n_nodes) <= VMEM_BUDGET, (
        "the walk kernel's blocks outgrow VMEM: take a smaller b_tile")
    lanes = lambda w: pl.BlockSpec((b_tile, w), lambda i: (i, 0))
    in_specs = [lanes(STATE_W), lanes(W),
                pl.BlockSpec(tables.shape, lambda i: (0, 0))]
    operands = [state, busy, tables]
    if allow_vec is not None:
        allow_nonminimal = None
        in_specs.append(lanes(1))
        operands.append(allow_vec.astype(jnp.int32).reshape(B, 1))
    kernel = functools.partial(_walk_kernel, cols=cols, n_nodes=n_nodes,
                               allow_nonminimal=allow_nonminimal)
    return pl.pallas_call(
        kernel,
        grid=(B // b_tile,),
        in_specs=in_specs,
        out_specs=[lanes(STATE_W), lanes(W)],
        out_shape=[jax.ShapeDtypeStruct((B, STATE_W), jnp.int32),
                   jax.ShapeDtypeStruct((B, W), jnp.int32)],
        interpret=interpret,
        name="scout_walk",
    )(*operands)
