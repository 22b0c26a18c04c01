"""Jitted discrete-resource SSD simulator over the table-driven design substrate.

Replaces MQSim's event-driven C++ core with a ``lax.scan`` over page-level
transactions in arrival order: each step computes the transaction's start time
from the *free-at* state of every resource it needs (plane, flash controller,
channel or mesh links), commits its occupancy, and emits completion/energy
stats.  Venice's path reservation runs the Algorithm-1 scout engine
(``core/scout.py``) inside the scan, retrying at the next link-free event when
a scout fails — exactly the paper's "retry immediately" policy (§4.2).

There is exactly ONE scan step function.  Designs are not code paths: each
design in ``repro.ssd.designs.REGISTRY`` lowers to padded tables
(``LaneTables``) over a unified resource vector ``[links | FCs | chips]``,
and the step consumes only those arrays — shared buses are 1-link "meshes"
with routing disabled (the scout degenerates to a zero-length path), pnSSD
is two candidate 1-link masks, NoSSD is a static XY-path mask, Venice builds
its mask with the scout at runtime.  ``simulate_sweep`` routes every lane
through the sweep planner (``repro.ssd.sweep_plan``): lanes are pooled per
cost class (statically-routed vs scout-routed), row-confined static lanes
are channel-decomposed, and lanes run as unbatched chunk-trimmed scans
dispatched asynchronously across the host CPU devices — all bit-identical
to the flat scan of ``simulate``.  Executables take the design tables as
*arguments*, so they are design-agnostic: changing the design set never
recompiles; one executable per (geometry, capacity bucket, cost class,
promotions, device) serves every lane, workload, config and phase.

Designs (see ``designs.REGISTRY`` for the spec + ablation docs of each)
  baseline        multi-channel shared bus (Table 1)
  pssd            Kim+ [15]: packetized, 2x channel bandwidth
  pnssd           Kim+ [15]: row+column shared buses (two paths per chip)
  nossd           Tavakkol+ [38]: 2D mesh, deterministic XY routing
  venice          the paper: scout path reservation + non-minimal adaptive
  venice_minimal  ablation: Venice with minimal-only adaptive routing
  venice_hold     ablation: circuit held across CMD+tR+transfer (the paper's
                  per-transfer reservation recovers these link-hours)
  venice_kscout   beyond-paper: race 3 scouts, commit the fewest-hop success
  ideal           path-conflict-free: a private channel per chip

Approximations vs MQSim (all documented in DESIGN.md §3): in-order commit per
transaction; single-gap backfill per shared resource (captures CMD-during-tR
and one-deep data backfill — the dominant pipelining in a real channel);
NoSSD's buffered wormhole modeled as transient circuits per packet phase.
"""
from __future__ import annotations

import functools
import os
import threading
import time
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.scout import make_tables, scout_route
from repro.core.topology import build_mesh
from repro.obs import spans as obs_spans
from repro.kernels import onehot
from repro.kernels.batched_step import lanes_2d, lane_tiled_step
from repro.kernels.ops import BatchRouteOut, route_dfs
from repro.kernels.scout_step import link_pad, pack_tables, walk_tile
from repro.ssd.config import SSDConfig, TICK_NS
from repro.ssd.designs import (
    DESIGNS,
    REGISTRY,
    LaneTables,
    mask_words_per_row,
    resolve_specs,
    sweep_layout_geom,
)

__all__ = [
    "DESIGNS", "TxnArrays", "StepOut", "SimResult", "simulate",
    "simulate_sweep",
]

_BIG = np.int32(2**30)
_MAX_TRIES = 64  # scout retry bound per reservation

# Reservation-failure timeout (ISSUE 8): a transaction whose every candidate
# path crosses a dead resource (``LaneTables.res_dead``) can never reserve.
# Statically-routed designs have no alternative to retry, so the bounded
# timeout-and-retry budget collapses to this one constant; scout designs
# first burn their real retry schedule (``_MAX_TRIES`` event-driven retries
# — the backoff is the advance to the next link-state change) and only a
# scout that still cannot reach the chip gives up.  Either way the
# transaction completes at ``t + FAIL_TIMEOUT`` with ``failed=True``, holds
# no path resources, and frees its plane at the timeout — permanent-failure
# accounting, not silent loss.  ~10.5 ms at the 10 ns tick.
FAIL_TIMEOUT = np.int32(1 << 20)

# Lane-step kernel backend for the batched runners.  "auto" (the
# default) compiles the Pallas kernels (``kernels.batched_step``,
# ``kernels.scout_step``) on an accelerator and keeps the one-hot XLA step
# on CPU, where Pallas has no compiler and interpret mode lowers to the
# same ops plus per-step call scaffolding.  "xla" forces the XLA step;
# "pallas" asks for the kernels, which on CPU can only run interpreted
# ("pallas-interpret", which tests pin against the XLA step).  An
# accelerator never runs a kernel interpreted.  Settable via the
# REPRO_LANE_BACKEND env var or ``benchmarks/run.py --lane-backend``.
LANE_BACKEND = os.environ.get("REPRO_LANE_BACKEND", "auto")
_LANE_BACKENDS = ("xla", "pallas", "pallas-interpret", "auto")
_ACCEL_BACKENDS = ("gpu", "tpu", "cuda", "rocm")


def resolve_lane_backend(setting: str | None = None) -> str:
    """Resolve ``setting`` (default: module ``LANE_BACKEND``) to a concrete
    backend name — "xla", "pallas" (compiled) or "pallas-interpret" —
    for the JAX backend actually in use."""
    s = setting if setting is not None else LANE_BACKEND
    if s not in _LANE_BACKENDS:
        raise ValueError(
            f"unknown lane backend {s!r}; pick from {_LANE_BACKENDS}")
    on_accel = jax.default_backend() in _ACCEL_BACKENDS
    if s == "pallas-interpret" and on_accel:
        raise ValueError(
            "lane backend 'pallas-interpret' would hide the kernels from "
            f"the {jax.default_backend()} device; use 'pallas' or 'auto'")
    if s == "auto":
        return "pallas" if on_accel else "xla"
    if s == "pallas" and not on_accel:
        return "pallas-interpret"
    return s

KIND_READ, KIND_WRITE, KIND_ERASE = 0, 1, 2


class TxnArrays(NamedTuple):
    """Page-level transactions, sorted by arrival (ticks)."""

    arrival: jnp.ndarray  # int32 [n]
    kind: jnp.ndarray  # int32 [n] 0=read 1=write 2=erase
    plane: jnp.ndarray  # int32 [n] global plane id
    node: jnp.ndarray  # int32 [n] chip / mesh node id
    row: jnp.ndarray  # int32 [n] channel id
    nbytes: jnp.ndarray  # int32 [n]
    op_ticks: jnp.ndarray  # int32 [n] tR/tPROG/tBERS by kind
    valid: jnp.ndarray  # bool  [n] padding mask


class StepOut(NamedTuple):
    completion: jnp.ndarray  # int32 ticks
    wait: jnp.ndarray  # int32 ticks spent waiting on the path (conflict time)
    conflict: jnp.ndarray  # bool — experienced a path conflict (fig. 13)
    hops: jnp.ndarray  # int32 (mesh designs; 0 for bus designs)
    tries: jnp.ndarray  # int32 scout attempts (venice)
    scout_steps: jnp.ndarray  # int32 DFS steps (venice)
    misroutes: jnp.ndarray  # int32 non-minimal hops on final path (venice)
    bus_hold: jnp.ndarray  # int32 ticks a shared bus was held
    link_hold: jnp.ndarray  # int32 link-ticks (sum over links held)
    failed: jnp.ndarray  # bool — permanent reservation failure (dead path)


# ---------------------------------------------------------------------------
# resource scheduling primitives
#
# Every time-shared resource (bus channel, mesh link, flash controller, chip
# I/O interface) is a triple of arrays (free_at, gap_s, gap_e): busy through
# ``free_at`` except one remembered idle gap [gap_s, gap_e).  The in-order
# scan can commit transfers far in the future (e.g. a write waiting on a
# 100 us tPROG), and the remembered gap keeps the resource's *current* idle
# capacity usable by later transactions instead of ratcheting free_at
# forward — the one-gap interval model is what keeps this O(1)-state
# simulator faithful to an event-driven scheduler to first order.
# ---------------------------------------------------------------------------


def _gap_avail(gs, ge, fa, e, d):
    """Earliest start >= e where a d-tick usage fits (gap or tail)."""
    s_gap = jnp.maximum(e, gs)
    fits = (s_gap + d) <= ge
    return jnp.where(fits, s_gap, jnp.maximum(e, fa))


def _gap_commit(gs, ge, fa, s, e2):
    """Carve the interval [s, e2) out; remember the larger leftover gap."""
    in_gap = (s >= gs) & (e2 <= ge)
    # inside the gap: keep the larger of the two leftover sides
    left_bigger = (s - gs) >= (ge - e2)
    g_gs = jnp.where(left_bigger, gs, e2)
    g_ge = jnp.where(left_bigger, s, ge)
    # appended at/after free_at: keep the larger of (old gap, new idle span)
    new_idle = jnp.maximum(s, fa) - fa
    keep_old = (ge - gs) >= new_idle
    a_gs = jnp.where(keep_old, gs, fa)
    a_ge = jnp.where(keep_old, ge, jnp.maximum(s, fa))
    a_fa = jnp.maximum(fa, e2)
    return (
        jnp.where(in_gap, g_gs, a_gs),
        jnp.where(in_gap, g_ge, a_ge),
        jnp.where(in_gap, fa, a_fa),
    )


def _avail1(res, i, e, d):
    free, gap_s, gap_e = res
    return _gap_avail(gap_s[i], gap_e[i], free[i], e, d)


def _commit1(res, i, s, e2, enable):
    free, gap_s, gap_e = res
    gs, ge, fa = _gap_commit(gap_s[i], gap_e[i], free[i], s, e2)
    return (
        free.at[i].set(jnp.where(enable, fa, free[i])),
        gap_s.at[i].set(jnp.where(enable, gs, gap_s[i])),
        gap_e.at[i].set(jnp.where(enable, ge, gap_e[i])),
    )


def _avail_all(res, e, d):
    """Vectorized earliest-start for every resource in the triple."""
    free, gap_s, gap_e = res
    return _gap_avail(gap_s, gap_e, free, e, d)


def _busy_at(res, t, d):
    """bool per resource: cannot host a d-tick usage starting exactly at t."""
    free, gap_s, gap_e = res
    free_ok = t >= free
    gap_ok = (t >= gap_s) & ((t + d) <= gap_e)
    return ~(free_ok | gap_ok)


def _commit_mask(res, mask, s, e2, enable):
    free, gap_s, gap_e = res
    gs, ge, fa = _gap_commit(gap_s, gap_e, free, s, e2)
    take = mask & enable
    return (
        jnp.where(take, fa, free),
        jnp.where(take, gs, gap_s),
        jnp.where(take, ge, gap_e),
    )


def _sched_gap(res, i, e, d, enable):
    """Schedule a d-tick usage of resource ``i`` at the earliest time >= e."""
    s = _avail1(res, i, e, d)
    s = jnp.where(enable, s, e)
    res = _commit1(res, i, s, s + d, enable)
    return s, res


def _triple(n: int):
    z = jnp.zeros((n,), jnp.int32)
    return (z, z, z)


def _ceil_div(a, b):
    return (a + b - 1) // b


# ---------------------------------------------------------------------------
# the one scan step — consumes only LaneTables arrays
# ---------------------------------------------------------------------------


# Per-design scalars that are promoted to compile-time constants when every
# lane of a sweep group agrees on the value (always true for 1-lane
# ``simulate`` and the common homogeneous sweeps).  XLA then folds the
# selects/arithmetic and dead-code-eliminates the untaken design variant's
# subgraph, so a homogeneous program is as lean as a hand-written one,
# while heterogeneous sweeps keep the scalars traced and stay fully generic.
_PROMOTABLE = (
    "hold", "allow_nonmin", "n_scouts", "fc_nearest", "count_bus",
    "ovh", "cmd_base_ns", "xfer_num", "xfer_den", "hop_ns",
    "d_est_hops", "d_est_pad",
)


def _make_step(lay, stables, scout_hop_ns: int, n_planes: int, k_max: int,
               has_static: bool, fixed: tuple):
    """Build the design-agnostic scan step.

    ``sp`` below is one lane's view of :class:`LaneTables` (the design axis
    is handled by ``vmap`` in ``_build_sweep``); everything the step knows
    about the design comes from those arrays.  The only static knobs are
    ``k_max`` (max scouts raced), the cost-class flag ``has_static`` (a
    statically-routed group compiles no scout machinery and a scout group
    no candidate scheduling), and ``fixed`` (values of ``_PROMOTABLE``
    scalars shared by every lane, or None when mixed) — each class's
    program is as lean as the seed's hand-written per-design steps.

    Returns ``(init_state, step)``; the two classes carry different scan
    state (the static class schedules over one unified resource vector, the
    scout class over separate link/FC/chip pools, narrow like the original
    hand-written Venice step).
    """
    L0, F0, R_pad = lay.L_pad, lay.F_pad, lay.R_pad
    n_fcs = lay.rows
    fixed = dict(zip(_PROMOTABLE, fixed))

    def fx(sp, name):
        v = fixed[name]
        return getattr(sp, name) if v is None else v

    def cmd_ticks(sp, hops):
        ns = fx(sp, "cmd_base_ns") + hops * fx(sp, "hop_ns")
        return jnp.maximum(_ceil_div(ns, TICK_NS), 1).astype(jnp.int32)

    def xfer_ticks(sp, nbytes, hops):
        ns = _ceil_div(nbytes * fx(sp, "xfer_num"), fx(sp, "xfer_den"))
        ns = ns + hops * fx(sp, "hop_ns")
        return _ceil_div(ns, TICK_NS).astype(jnp.int32)

    def path_sched(res, mask, e, d):
        """Earliest common start >= e for a d-tick usage of every masked
        resource.  Per-resource availability first; if the joint candidate
        doesn't fit everywhere, fall back to the masked free_at tail."""
        avail = _avail_all(res, e, d)
        s1 = jnp.max(jnp.where(mask, avail, 0))
        s1 = jnp.maximum(s1, e)
        ok = ~jnp.any(_busy_at(res, s1, d) & mask)
        s_tail = jnp.maximum(e, jnp.max(jnp.where(mask, res[0], 0)))
        return jnp.where(ok, s1, s_tail)

    def fc_select(avail, dist_row, tcand):
        """Paper §4.2: closest FC *available now*, else earliest-available
        (availability = can host a d_est-tick transfer, gap-aware)."""
        free_now = avail <= tcand
        any_free = jnp.any(free_now)
        by_dist = jnp.argmin(jnp.where(free_now, dist_row, _BIG))
        by_time = jnp.argmin(avail)
        fc = jnp.where(any_free, by_dist, by_time).astype(jnp.int32)
        t0 = jnp.maximum(tcand, avail[fc])
        return fc, t0

    def eval_static_cand(sp, res, tx, is_read, t0, fc, cand, enable):
        """One statically-routed candidate: phase 0 (command, +data for
        writes), flash op, phase 1 (read data) on one combined mask.
        A candidate whose mask touches a dead resource is value-dead:
        its commits are disabled and ``dead`` is returned for selection."""
        mask = sp.cmask[fc, tx.node, cand]
        dead = jnp.any(mask & sp.res_dead)
        enable = enable & ~dead
        hops = sp.hops[fc, tx.node, cand]
        cmd = cmd_ticks(sp, hops)
        xfer = xfer_ticks(sp, tx.nbytes, hops)
        ovh = fx(sp, "ovh")
        d0 = ovh + cmd + jnp.where(is_read, 0, xfer)
        s0 = path_sched(res, mask, t0, d0)
        res = _commit_mask(res, mask, s0, s0 + d0, enable)
        op_end = s0 + d0 + tx.op_ticks
        d1 = ovh + xfer
        s1 = path_sched(res, mask, op_end, d1)
        res = _commit_mask(res, mask, s1, s1 + d1, enable & is_read)
        done = jnp.where(is_read, s1 + d1, op_end)
        wait = (s0 - t0) + jnp.where(is_read, s1 - op_end, 0)
        occ = d0 + jnp.where(is_read, d1, 0)  # resource-held ticks
        return res, done, wait, occ, hops, dead

    def scout_until_success(links3, sp, src, dst, t0, rng, d_hold):
        """Retry the scout at successive link-free events until it reserves.

        A link is busy for the scout if it cannot host a ``d_hold``-tick
        reservation starting now (gap-aware).  ``k_max`` scouts race per
        try with independent tie-break streams; scouts beyond the lane's
        ``n_scouts`` are masked out (their rng is not advanced), so a
        1-scout lane in a k-scout sweep is bit-identical to a 1-scout
        program."""
        n_scouts = fx(sp, "n_scouts")
        allow = fx(sp, "allow_nonmin")
        # dead links look permanently busy to the DFS, so the scout routes
        # AROUND faults (the whole point of path diversity); an all-False
        # res_dead makes this OR a no-op — fault-free bit-identity
        dead_links = sp.res_dead[:L0]

        def try_once(t, rng):
            busy = _busy_at(links3, t, d_hold) | dead_links
            best = None
            for k in range(k_max):
                rng_adv = (
                    rng * jnp.uint32(747796405) + jnp.uint32(2891336453)
                ) | jnp.uint32(1)
                active = k < n_scouts  # bool or traced bool
                rng = jnp.where(active, rng_adv, rng)
                res = scout_route(stables, src, dst, busy, rng, allow)
                if best is None:
                    best = res
                else:
                    take = res.success & active & (
                        (~best.success) | (res.hops < best.hops)
                    )
                    best = jax.tree_util.tree_map(
                        lambda a, b: jnp.where(take, a, b), res, best
                    )
            return best, rng

        res0, rng = try_once(t0, rng)

        def cond(carry):
            res, t, rng, tries = carry
            return (~res.success) & (tries < _MAX_TRIES)

        def body(carry):
            res, t, rng, tries = carry
            # advance to the next potential link-state change:
            # a free_at passing, or an idle gap opening
            free, gap_s, _ = links3
            ev = jnp.minimum(
                jnp.min(jnp.where(free > t, free, _BIG)),
                jnp.min(jnp.where(gap_s > t, gap_s, _BIG)),
            )
            t_next = jnp.maximum(ev, t + 1)
            t_next = jnp.where(tries + 1 >= _MAX_TRIES, jnp.max(free), t_next)
            res, rng = try_once(t_next, rng)
            return res, t_next, rng, tries + 1

        res, t, rng, tries = jax.lax.while_loop(
            cond, body, (res0, t0, rng, jnp.int32(1))
        )
        return res, t, rng, tries

    def d_est_of(sp, tx, is_read, hold):
        """Duration estimate for availability checks (FC selection + scout)."""
        d_est = (xfer_ticks(sp, tx.nbytes, fx(sp, "d_est_hops"))
                 + fx(sp, "d_est_pad"))
        if hold is not False:  # hold lanes park the circuit across reads' tR
            d_est = d_est + jnp.where(
                jnp.logical_and(hold, is_read), tx.op_ticks, 0
            )
        return d_est

    def static_step(sp, state, tx: TxnArrays):
        # ---- statically-routed lanes: <=2 candidate combined masks over
        # the unified [links | FCs | chips] resource vector ----
        plane_free, res = state
        is_read = tx.kind == KIND_READ
        tcand = jnp.maximum(tx.arrival, plane_free[tx.plane])
        fc_nearest = fx(sp, "fc_nearest")
        count_bus = fx(sp, "count_bus")

        d_est = d_est_of(sp, tx, is_read, fx(sp, "hold"))
        free, gs, ge = res
        sl = slice(L0, L0 + F0)
        avail = _gap_avail(gs[sl], ge[sl], free[sl], tcand, d_est)
        avail = jnp.where(sp.fc_valid, avail, _BIG)
        fc_near, t0_near = fc_select(avail, sp.dist[:, tx.node], tcand)
        t0 = jnp.where(fc_nearest, t0_near, tcand)

        fcA = jnp.where(fc_nearest, fc_near, sp.fc_fixed[tx.node, 0])
        fcB = jnp.where(fc_nearest, fc_near, sp.fc_fixed[tx.node, 1])
        cand2 = sp.cand2_ok[tx.node]
        resA, doneA, waitA, occA, hopsA, deadA = eval_static_cand(
            sp, res, tx, is_read, t0, fcA, 0, tx.valid
        )
        resB, doneB, waitB, occB, hopsB, deadB = eval_static_cand(
            sp, res, tx, is_read, t0, fcB, 1, tx.valid & cand2
        )
        # a dead candidate never wins selection; when every candidate is
        # dead, the reservation fails permanently (FAIL_TIMEOUT accounting).
        # With all-False res_dead this reduces exactly to the fault-free
        # ``doneA <= where(cand2, doneB, _BIG)`` — bit-identical outputs.
        useA = jnp.where(deadA, _BIG, doneA) <= jnp.where(
            cand2 & ~deadB, doneB, _BIG
        )
        failed = deadA & (deadB | ~cand2)
        res = jax.tree_util.tree_map(
            lambda a, b: jnp.where(useA, a, b), resA, resB
        )
        done = jnp.where(useA, doneA, doneB)
        wait = jnp.where(useA, waitA, waitB)
        occ = jnp.where(useA, occA, occB)
        hops_o = jnp.where(useA, hopsA, hopsB)
        done = jnp.where(failed, tcand + FAIL_TIMEOUT, done)
        wait = jnp.where(failed, FAIL_TIMEOUT, wait)
        occ = jnp.where(failed, 0, occ)
        hops_o = jnp.where(failed, 0, hops_o)
        plane_free = plane_free.at[tx.plane].set(
            jnp.where(tx.valid, done, plane_free[tx.plane])
        )
        out = StepOut(
            completion=done,
            wait=wait,
            conflict=wait > 0,
            hops=hops_o,
            tries=jnp.int32(1),
            scout_steps=jnp.int32(0),
            misroutes=jnp.int32(0),
            bus_hold=jnp.where(count_bus, occ, 0),
            link_hold=jnp.where(count_bus, 0, hops_o * occ),
            failed=failed,
        )
        return (plane_free, res), out

    def scout_step(sp, state, tx: TxnArrays):
        # ---- scout-routed lanes (Venice §4): per-transfer circuit over
        # separate link/FC/chip pools (narrow state keeps the hot scan as
        # lean as a hand-written Venice step) ----
        plane_free, links, fcs, chips, rng = state
        is_read = tx.kind == KIND_READ
        tcand = jnp.maximum(tx.arrival, plane_free[tx.plane])
        hold = fx(sp, "hold")

        d_est = d_est_of(sp, tx, is_read, hold)
        avail = _avail_all(fcs, tcand, d_est)
        # dead FCs (fc_valid lowered False by the FaultSpec) are never
        # selected; all-valid lanes see ``where(True, avail, _BIG)`` — a
        # no-op, so the fault-free program output is unchanged
        avail = jnp.where(sp.fc_valid[:n_fcs], avail, _BIG)
        fc, t0 = fc_select(avail, sp.dist[:n_fcs, tx.node], tcand)
        src = sp.fc_node[fc]
        min_hops = sp.dist[fc, tx.node]
        cmd_pkt = cmd_ticks(sp, min_hops)  # read cmd: scout-sized packet
        # reads: command packet now; data-phase scout at tR completion
        # (paper mode only — hold mode keeps one circuit for everything)
        en_cmd = tx.valid & is_read & jnp.logical_not(hold)
        s_cmd, fcs = _sched_gap(fcs, fc, t0, cmd_pkt, en_cmd)
        ready_r = s_cmd + cmd_pkt + tx.op_ticks  # data in page buffer
        # the data transfer additionally needs this FC and the chip's I/O
        # interface (the FC tracks chip status and only scouts when the
        # transfer can actually start)
        t_nonread = jnp.maximum(t0, _avail1(chips, tx.node, t0, d_est))
        t_read = jnp.maximum(
            jnp.maximum(ready_r, _avail1(fcs, fc, ready_r, d_est)),
            _avail1(chips, tx.node, ready_r, d_est),
        )
        t_xfer_req = jnp.where(is_read, t_read, t_nonread)
        t_scout = jnp.where(hold, t0, t_xfer_req)
        sres, t_resv, rng, tries = scout_until_success(
            links, sp, src, tx.node, t_scout, rng, d_est
        )
        hops_o = sres.hops
        rtt = _ceil_div((sres.steps + hops_o) * scout_hop_ns, TICK_NS)
        start = t_resv + rtt.astype(jnp.int32)
        cmd_v = cmd_ticks(sp, hops_o)
        xfer_v = xfer_ticks(sp, tx.nbytes, hops_o)
        # paper mode: read = backward data; write/erase = fwd cmd+data
        dur_p = jnp.where(is_read, xfer_v, cmd_v + xfer_v)
        end_p = start + dur_p
        done_p = jnp.where(is_read, end_p, end_p + tx.op_ticks)
        wait_p = (s_cmd - t0) + (start - t_xfer_req)
        # hold mode: one circuit across CMD + flash op + transfer
        done_r_h = start + cmd_v + tx.op_ticks + xfer_v
        data_end_w = start + cmd_v + xfer_v
        circuit_end = jnp.where(is_read, done_r_h, data_end_w)
        done_h = jnp.where(is_read, done_r_h, data_end_w + tx.op_ticks)
        commit_end = jnp.where(hold, circuit_end, end_p)
        done = jnp.where(hold, done_h, done_p)
        wait = jnp.where(hold, start - t0, wait_p)
        # permanent failure: the scout burned its whole retry schedule (the
        # final try runs against an otherwise-idle mesh, so a fault-free
        # lane can never get here) — no circuit is committed, the txn
        # times out, and its plane frees at the timeout
        fail = ~sres.success
        ok = tx.valid & sres.success
        done = jnp.where(fail, tcand + FAIL_TIMEOUT, done)
        wait = jnp.where(fail, FAIL_TIMEOUT, wait)
        links = _commit_mask(links, sres.path_mask, t_resv, commit_end, ok)
        fcs = _commit1(fcs, fc, t_resv, commit_end, ok)
        chips = _commit1(chips, tx.node, t_resv, commit_end, ok)
        plane_free = plane_free.at[tx.plane].set(
            jnp.where(tx.valid, done, plane_free[tx.plane])
        )
        out = StepOut(
            completion=done,
            wait=wait,
            conflict=(tries > 1) | fail,
            hops=hops_o,
            tries=tries,
            scout_steps=sres.steps,
            misroutes=sres.misroutes,
            bus_hold=jnp.int32(0),
            link_hold=jnp.where(fail, 0, hops_o * (commit_end - t_resv)),
            failed=fail,
        )
        return (plane_free, links, fcs, chips, rng), out

    if has_static:
        def init_state(seed):
            return (jnp.zeros((n_planes,), jnp.int32), _triple(R_pad))

        return init_state, static_step

    def init_state(seed):
        return (
            jnp.zeros((n_planes,), jnp.int32),
            _triple(L0),
            _triple(n_fcs),
            _triple(lay.n_nodes),
            jnp.asarray(seed, jnp.uint32),
        )

    return init_state, scout_step


# ---------------------------------------------------------------------------
# harness
# ---------------------------------------------------------------------------


def _geom_sig(cfg: SSDConfig) -> tuple:
    """The slice of the config the compiled program actually depends on.

    Latencies, page size and channel rates reach the program as traced data
    (txn arrays / LaneTables), so perf- and cost-optimized configs of the
    same geometry share every executable."""
    return (cfg.rows, cfg.cols, cfg.dies_per_chip, cfg.planes_per_die,
            int(round(cfg.scout_flit_ns)))


def _promotions(tables) -> tuple:
    """Common value of each _PROMOTABLE scalar across the group's lanes
    (read from the lowered tables), else None."""
    out = []
    for name in _PROMOTABLE:
        vals = np.asarray(getattr(tables, name))
        if np.all(vals == vals.flat[0]):
            out.append(vals.flat[0].item())  # hashable python bool/int
        else:
            out.append(None)
    return tuple(out)


def _skip_out(tx: TxnArrays) -> StepOut:
    """StepOut emitted for padded (invalid) transactions."""
    return StepOut(
        completion=tx.arrival,
        wait=jnp.int32(0),
        conflict=jnp.bool_(False),
        hops=jnp.int32(0),
        tries=jnp.int32(0),
        scout_steps=jnp.int32(0),
        misroutes=jnp.int32(0),
        bus_hold=jnp.int32(0),
        link_hold=jnp.int32(0),
        failed=jnp.bool_(False),
    )


# ---------------------------------------------------------------------------
# chunked, trimmed, shardable runners
#
# Transactions are packed into *capacity*-sized buffers (few coarse
# power-of-4 buckets, to bound the number of distinct executables) but the
# scan itself is a ``fori_loop`` over CHUNK-step ``lax.scan`` chunks with a
# *traced* trip count: one compiled program serves every trace length, and
# execute time scales with the valid length rounded up to CHUNK — not with
# the capacity bucket.  Each lane (its tables, seed and transaction stream
# are all arguments) runs UNBATCHED inside its device shard of a
# ``shard_map`` group — one lane per host CPU device; the sweep planner
# sorts lanes from many workloads/configs/channel-rows by length so the
# lanes sharing a group's barrier are of similar cost.
# ---------------------------------------------------------------------------

CHUNK = 1024  # scan-chunk granularity; trims pad waste to < one chunk


def host_device_count() -> int:
    """Lane shards available (== --xla_force_host_platform_device_count)."""
    return len(jax.devices())


@functools.lru_cache(maxsize=None)
def _lane_mesh(n_shards: int) -> Mesh:
    return Mesh(np.array(jax.devices()[:n_shards]), ("lanes",))


def _zero_out(capacity: int) -> StepOut:
    z = jnp.zeros((capacity,), jnp.int32)
    return StepOut(
        completion=z, wait=z, conflict=jnp.zeros((capacity,), jnp.bool_),
        hops=z, tries=z, scout_steps=z, misroutes=z, bus_hold=z, link_hold=z,
        failed=jnp.zeros((capacity,), jnp.bool_),
    )


def _make_lane_run(init_state, step, capacity: int):
    """One lane: chunked scan with a dynamic (traced) chunk count."""

    def lane_run(sp, seed, txns: TxnArrays, n_chunks):
        state = init_state(seed)

        def scan_step(st, tx):
            def real(st):
                return step(sp, st, tx)

            def skip(st):
                return st, _skip_out(tx)

            return jax.lax.cond(tx.valid, real, skip, st)

        def chunk_body(c, carry):
            st, buf = carry
            off = c * CHUNK
            txc = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, off, CHUNK, 0),
                txns,
            )
            st, outs = jax.lax.scan(scan_step, st, txc)
            buf = jax.tree_util.tree_map(
                lambda b, o: jax.lax.dynamic_update_slice_in_dim(b, o, off, 0),
                buf, outs,
            )
            return st, buf

        _, buf = jax.lax.fori_loop(
            0, n_chunks, chunk_body, (state, _zero_out(capacity))
        )
        return buf

    return lane_run


def _make_lane_run_carry(step, capacity: int):
    """State-carrying lane runner (the streaming engine's variant).

    Identical scan body to :func:`_make_lane_run`, but the scan state is an
    *argument* and is returned alongside the output buffer — the streaming
    engine (``repro.ssd.stream``) threads it across window boundaries
    (rebased host-side by the window span).  A window run with the zero
    initial state is bit-identical to the plain runner: same step, same
    chunking, same skip semantics.
    """

    def lane_run(sp, state, txns: TxnArrays, n_chunks):
        def scan_step(st, tx):
            def real(st):
                return step(sp, st, tx)

            def skip(st):
                return st, _skip_out(tx)

            return jax.lax.cond(tx.valid, real, skip, st)

        def chunk_body(c, carry):
            st, buf = carry
            off = c * CHUNK
            txc = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, off, CHUNK, 0),
                txns,
            )
            st, outs = jax.lax.scan(scan_step, st, txc)
            buf = jax.tree_util.tree_map(
                lambda b, o: jax.lax.dynamic_update_slice_in_dim(b, o, off, 0),
                buf, outs,
            )
            return st, buf

        return jax.lax.fori_loop(
            0, n_chunks, chunk_body, (state, _zero_out(capacity))
        )

    return lane_run


def _step_for(sig: tuple, k_max: int, has_scout: bool, fixed: tuple):
    rows, cols, dies, planes_per_die, scout_hop_ns = sig
    topo = build_mesh(rows, cols)
    n_planes = rows * cols * dies * planes_per_die
    lay = sweep_layout_geom(rows, cols)
    stables = make_tables(topo)
    return _make_step(lay, stables, scout_hop_ns, n_planes, k_max,
                      not has_scout, fixed)


@functools.lru_cache(maxsize=None)
def _build_group_fn(sig: tuple, capacity: int, k_max: int,
                    has_scout: bool, fixed: tuple, n_shards: int):
    """One design-agnostic SPMD program per (geometry, capacity bucket,
    cost class, promotions, shard count).  Tables/seeds/txns/chunk-counts
    are all per-lane *arguments*, so every group of the pool — any designs,
    any workloads, any configs of the geometry, any phase — reuses it.

    A group carries exactly one lane per device shard, and the shard body
    SQUEEZES its lane axis before running the scan: the lane stays
    unbatched, which is load-bearing for CPU performance — a real
    ``lax.cond`` skip (never a batched select that executes both branches)
    and dynamic-slice resource indexing (``vmap`` would lower the per-step
    state updates to generic batched gather/scatter kernels, measured ~50x
    slower per scout step).  Multi-core parallelism comes from the shards
    executing in parallel inside the one program, not from batching; each
    shard's ``fori_loop`` trip count is its own lane's."""
    init_state, step = _step_for(sig, k_max, has_scout, fixed)
    lane_run = _make_lane_run(init_state, step, capacity)

    def one(sp, seed, txns, n_chunks):
        take0 = lambda a: a[0]
        out = lane_run(
            jax.tree_util.tree_map(take0, sp), seed[0],
            jax.tree_util.tree_map(take0, txns), n_chunks[0],
        )
        return jax.tree_util.tree_map(lambda a: a[None], out)

    if n_shards > 1:
        spec = (P("lanes"),) * 4
        fn = jax.shard_map(one, mesh=_lane_mesh(n_shards), in_specs=spec,
                           out_specs=P("lanes"), check_vma=False)
    else:
        fn = one
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _build_group_fn_carry(sig: tuple, capacity: int, k_max: int,
                          has_scout: bool, fixed: tuple, n_shards: int):
    """State-carrying variant of :func:`_build_group_fn` (``"lanec"``).

    The scan state rides as a per-lane argument and comes back with the
    outputs, so one executable serves every window of a streamed replay:
    the streaming engine rebases the returned state host-side and feeds it
    to the next window's dispatch.  Same shard/squeeze discipline as the
    plain group fn — the lane stays unbatched inside its shard."""
    _, step = _step_for(sig, k_max, has_scout, fixed)
    lane_run = _make_lane_run_carry(step, capacity)

    def one(sp, state, txns, n_chunks):
        take0 = lambda a: a[0]
        st, out = lane_run(
            jax.tree_util.tree_map(take0, sp),
            jax.tree_util.tree_map(take0, state),
            jax.tree_util.tree_map(take0, txns), n_chunks[0],
        )
        add = lambda a: a[None]
        return (
            jax.tree_util.tree_map(add, st),
            jax.tree_util.tree_map(add, out),
        )

    if n_shards > 1:
        spec = (P("lanes"),) * 4
        fn = jax.shard_map(one, mesh=_lane_mesh(n_shards), in_specs=spec,
                           out_specs=P("lanes"), check_vma=False)
    else:
        fn = one
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# stacked small-lane variant: K lanes per shard, executed SEQUENTIALLY
#
# A pool of many tiny lanes (the QoS tail phase: hundreds of 1-2 chunk
# scans) used to pay one dispatch barrier per n_shards lanes.  ``lax.map``
# runs K lanes per shard one after another *inside* one program: the inner
# scan stays unbatched (``lax.map`` is a scan, not a vmap — no batched
# gather/scatter lowering), so per-step cost is identical; only the
# dispatch count drops K-fold.  Used for scout-routed small lanes; the
# statically-routed ones get the truly batched runner below.
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _build_stack_fn(sig: tuple, capacity: int, K: int, k_max: int,
                    has_scout: bool, fixed: tuple, n_shards: int):
    init_state, step = _step_for(sig, k_max, has_scout, fixed)
    lane_run = _make_lane_run(init_state, step, capacity)

    def one(sp, seed, txns, n_chunks):  # leading axis [K] per shard
        def run1(args):
            sp1, s1, t1, n1 = args
            return lane_run(sp1, s1, t1, n1)

        return jax.lax.map(run1, (sp, seed, txns, n_chunks))

    if n_shards > 1:
        spec = (P("lanes"),) * 4
        fn = jax.shard_map(one, mesh=_lane_mesh(n_shards), in_specs=spec,
                           out_specs=P("lanes"), check_vma=False)
    else:
        fn = one
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# gather-free batched small-lane runner (statically-routed lanes)
#
# PR 3's negative result — vmap-batched lanes ~50x slower per step on CPU —
# was a property of the *lowering*, not of batching: under vmap the
# per-step table lookups become generic batched gathers, the state updates
# batched scatters, and the validity ``cond`` a both-branches select.  The
# batched step below contains none of those:
#
#   * node-indexed design tables (cmask/hops/dist/cand2/fc_fixed) ride in
#     per lane, node-major and bit-packed along the resource axis
#     (``designs.node_tables``); the run gathers each chunk's rows per
#     transaction once, outside the inner scan (``_gather_node_rows``), so
#     the step itself reads them as sliced inputs;
#   * the two state-dependent lookups (plane free-at, live FC choice) are
#     one-hot compare-and-reduce (``repro.kernels.onehot``, the scout-
#     kernel trick) — exact for int32, no gather;
#   * validity is masked arithmetic: commits/updates already carry an
#     ``enable`` lane, and the skip-output substitution is a ``where`` —
#     bit-identical to the unbatched ``lax.cond`` skip because an invalid
#     step's state writes are all disabled.
#
# One dispatch now serves a whole batch of small lanes (the dispatch-bound
# tail phase collapses ~10x), while every per-lane result stays bit-exact
# vs the unbatched scan (pinned for every statically-routed design in
# tests/test_batched_lanes.py).  Scout lanes are excluded: their DFS
# while-loop diverges per lane (use the stacked variant above).
# ---------------------------------------------------------------------------


class BatchScalars(NamedTuple):
    """Per-lane design scalars of a batched group ([B], order of
    ``_PROMOTABLE``) plus the FC validity row ([B, F_pad]) and the
    failed-resource mask ([B, R_pad], all-False when fault-free)."""

    hold: jnp.ndarray
    allow_nonmin: jnp.ndarray
    n_scouts: jnp.ndarray
    fc_nearest: jnp.ndarray
    count_bus: jnp.ndarray
    ovh: jnp.ndarray
    cmd_base_ns: jnp.ndarray
    xfer_num: jnp.ndarray
    xfer_den: jnp.ndarray
    hop_ns: jnp.ndarray
    d_est_hops: jnp.ndarray
    d_est_pad: jnp.ndarray
    fc_valid: jnp.ndarray
    res_dead: jnp.ndarray


class BatchNodeTables(NamedTuple):
    """Per-lane node-indexed tables of a batched static group, lane-major
    [B, N, ...] (each lane's ``designs.node_tables``)."""

    mask_words: jnp.ndarray  # int32 [B, N, F_pad, 2, ceil(R_pad/32)]
    hops: jnp.ndarray  # int32 [B, N, F_pad, 2]
    dist: jnp.ndarray  # int32 [B, N, F_pad]
    cand2: jnp.ndarray  # bool  [B, N]
    fc_fixed: jnp.ndarray  # int32 [B, N, 2]


class BatchTxnTables(NamedTuple):
    """One chunk's node tables resolved per transaction, time-major
    [CHUNK, B, K]: the rows of :class:`BatchNodeTables` at each
    transaction's node, trailing axes flattened (the step reads the
    lane-major 2D form of ``kernels.batched_step.lanes_2d``)."""

    mask_words: jnp.ndarray  # int32 [CHUNK, B, F_pad * 2 * ceil(R_pad/32)]
    hops: jnp.ndarray  # int32 [CHUNK, B, F_pad * 2]
    dist: jnp.ndarray  # int32 [CHUNK, B, F_pad]
    cand2: jnp.ndarray  # bool  [CHUNK, B, 1]
    fc_fixed: jnp.ndarray  # int32 [CHUNK, B, 2]


def _node_rows(nt: BatchNodeTables) -> BatchNodeTables:
    """Each table [B, N, ...] as flat rows [B * N, K], lane-major."""
    return BatchNodeTables(*(t.reshape(t.shape[0] * t.shape[1], -1)
                             for t in nt))


def _gather_node_rows(rows: BatchNodeTables, node) -> BatchTxnTables:
    """``out[t, b] = rows[b * N + node[t, b]]`` for every table of
    :func:`_node_rows`, with ``node`` [C, B] a chunk's transaction nodes:
    one XLA gather per table.  Padding slots (node 0) read row 0, which
    the step's validity masking ignores."""
    B = node.shape[1]
    idx = node + (rows.cand2.shape[0] // B) * jnp.arange(B, dtype=node.dtype)
    return BatchTxnTables(*(jnp.take(r, idx, axis=0, mode="clip")
                            for r in rows))


def _make_batched_static_step(lay, n_planes: int, fixed: tuple):
    """The statically-routed scan step over a lane batch [B].

    Mirrors ``static_step`` in ``_make_step`` operation for operation
    (all int32 — the one-hot reductions and masked selects are exact, so
    batched == unbatched bit-for-bit); consult that function for the
    modeling semantics.  Every input is in the lane-major 2D form of
    :func:`repro.kernels.batched_step.lanes_2d` — per-lane scalars are
    [B, 1] columns, bools are 0/1 int32 — and so is every output, which
    is the layout the TPU kernel compiler lowers.  ``xs`` is
    ``(TxnArrays, BatchTxnTables)`` for this step.
    """
    L0, F0, R = lay.L_pad, lay.F_pad, lay.R_pad
    W = mask_words_per_row(R)
    fixed = dict(zip(_PROMOTABLE, fixed))

    def fx(sp, name):
        v = fixed[name]
        return getattr(sp, name) if v is None else v

    def flag(sp, name):
        v = fx(sp, name)
        return v if isinstance(v, bool) else v != 0

    def cmd_ticks(sp, hops):
        ns = fx(sp, "cmd_base_ns") + hops * fx(sp, "hop_ns")
        return jnp.maximum(_ceil_div(ns, TICK_NS), 1).astype(jnp.int32)

    def xfer_ticks(sp, nbytes, hops):
        ns = _ceil_div(nbytes * fx(sp, "xfer_num"), fx(sp, "xfer_den"))
        ns = ns + hops * fx(sp, "hop_ns")
        return _ceil_div(ns, TICK_NS).astype(jnp.int32)

    def iota(B, n):
        return jax.lax.broadcasted_iota(jnp.int32, (B, n), 1)

    def any_(mask):
        return jnp.max(jnp.where(mask, 1, 0), axis=1, keepdims=True) > 0

    def take(table, idx):
        """table[b, idx[b]] as a [B, 1] column (0 when out of range)."""
        hit = iota(*table.shape) == idx
        return jnp.sum(jnp.where(hit, table, 0), axis=1, keepdims=True)

    def path_sched(res, mask, e, d):
        free, gap_s, gap_e = res
        avail = _gap_avail(gap_s, gap_e, free, e, d)
        s1 = jnp.max(jnp.where(mask, avail, 0), axis=1, keepdims=True)
        s1 = jnp.maximum(s1, e)
        ok = ~any_(_busy_at(res, s1, d) & mask)
        s_tail = jnp.maximum(
            e, jnp.max(jnp.where(mask, free, 0), axis=1, keepdims=True))
        return jnp.where(ok, s1, s_tail)

    def commit_mask(res, mask, s, e2, enable):
        free, gap_s, gap_e = res
        gs, ge, fa = _gap_commit(gap_s, gap_e, free, s, e2)
        take_ = mask & enable
        return (
            jnp.where(take_, fa, free),
            jnp.where(take_, gs, gap_s),
            jnp.where(take_, ge, gap_e),
        )

    def step(sp: BatchScalars, state, xs):
        tx, tt = xs
        plane_free, res = state
        B = plane_free.shape[0]
        valid = tx.valid != 0
        is_read = tx.kind == KIND_READ
        tcand = jnp.maximum(tx.arrival, take(plane_free, tx.plane))
        fc_nearest = flag(sp, "fc_nearest")
        hold = flag(sp, "hold")

        d_est = (xfer_ticks(sp, tx.nbytes, fx(sp, "d_est_hops"))
                 + fx(sp, "d_est_pad"))
        if hold is not False:
            d_est = d_est + jnp.where(
                jnp.logical_and(hold, is_read), tx.op_ticks, 0
            )
        free, gs, ge = res
        sl = slice(L0, L0 + F0)
        avail = _gap_avail(gs[:, sl], ge[:, sl], free[:, sl], tcand, d_est)
        avail = jnp.where(sp.fc_valid != 0, avail, _BIG)
        free_now = avail <= tcand
        by_dist = onehot.argmin(jnp.where(free_now, tt.dist, _BIG),
                                keepdims=True)
        by_time = onehot.argmin(avail, keepdims=True)
        fc_near = jnp.where(any_(free_now), by_dist, by_time)
        t0_near = jnp.maximum(tcand, take(avail, fc_near))
        t0 = jnp.where(fc_nearest, t0_near, tcand)

        fcA = jnp.where(fc_nearest, fc_near, tt.fc_fixed[:, 0:1])
        fcB = jnp.where(fc_nearest, fc_near, tt.fc_fixed[:, 1:2])
        cand2 = tt.cand2 != 0
        # bit r of a candidate mask is bit r % 32 of its word r // 32
        iota_r = iota(B, R)
        word_of, bit_of = iota_r >> 5, iota_r & 31

        def eval_cand(res, cand, fc, enable):
            slot = fc * 2 + cand  # (fc, cand) slot of the [F0, 2] tables
            mask = jnp.zeros((B, R), bool)
            for w in range(W):
                word = take(tt.mask_words, slot * W + w)
                bits = jax.lax.shift_right_logical(word, bit_of) & 1
                mask = mask | ((word_of == w) & (bits != 0))
            dead = any_(mask & (sp.res_dead != 0))
            enable = enable & ~dead
            hops = take(tt.hops, slot)
            cmd = cmd_ticks(sp, hops)
            xfer = xfer_ticks(sp, tx.nbytes, hops)
            ovh = fx(sp, "ovh")
            d0 = ovh + cmd + jnp.where(is_read, 0, xfer)
            s0 = path_sched(res, mask, t0, d0)
            res = commit_mask(res, mask, s0, s0 + d0, enable)
            op_end = s0 + d0 + tx.op_ticks
            d1 = ovh + xfer
            s1 = path_sched(res, mask, op_end, d1)
            res = commit_mask(res, mask, s1, s1 + d1, enable & is_read)
            done = jnp.where(is_read, s1 + d1, op_end)
            wait = (s0 - t0) + jnp.where(is_read, s1 - op_end, 0)
            occ = d0 + jnp.where(is_read, d1, 0)
            return res, done, wait, occ, hops, dead

        resA, doneA, waitA, occA, hopsA, deadA = eval_cand(res, 0, fcA, valid)
        resB, doneB, waitB, occB, hopsB, deadB = eval_cand(res, 1, fcB,
                                                           valid & cand2)
        # mirrors the unbatched static step's dead-candidate selection
        useA = jnp.where(deadA, _BIG, doneA) <= jnp.where(
            cand2 & ~deadB, doneB, _BIG
        )
        failed = deadA & (deadB | ~cand2)
        res = jax.tree_util.tree_map(
            lambda a, b: jnp.where(useA, a, b), resA, resB
        )
        done = jnp.where(useA, doneA, doneB)
        wait = jnp.where(useA, waitA, waitB)
        occ = jnp.where(useA, occA, occB)
        hops_o = jnp.where(useA, hopsA, hopsB)
        done = jnp.where(failed, tcand + FAIL_TIMEOUT, done)
        wait = jnp.where(failed, FAIL_TIMEOUT, wait)
        occ = jnp.where(failed, 0, occ)
        hops_o = jnp.where(failed, 0, hops_o)
        upd = (iota(B, n_planes) == tx.plane) & valid
        plane_free = jnp.where(upd, done, plane_free)
        cb = jnp.logical_and(flag(sp, "count_bus"), True)
        zero = jnp.zeros_like(done)
        out = StepOut(
            completion=jnp.where(valid, done, tx.arrival),
            wait=jnp.where(valid, wait, 0),
            conflict=jnp.where(valid & (wait > 0), 1, 0),
            hops=jnp.where(valid, hops_o, 0),
            tries=jnp.where(valid, 1, 0),
            scout_steps=zero,
            misroutes=zero,
            bus_hold=jnp.where(valid & cb, occ, 0),
            link_hold=jnp.where(valid & jnp.logical_not(cb),
                                hops_o * occ, 0),
            failed=jnp.where(valid & failed, 1, 0),
        )
        return (plane_free, res), out

    return step


def _zero_out_tm(capacity: int, B: int) -> StepOut:
    z = jnp.zeros((capacity, B), jnp.int32)
    return StepOut(
        completion=z, wait=z,
        conflict=jnp.zeros((capacity, B), jnp.bool_),
        hops=z, tries=z, scout_steps=z, misroutes=z, bus_hold=z, link_hold=z,
        failed=jnp.zeros((capacity, B), jnp.bool_),
    )


def _make_batched_run(step, capacity: int, n_planes: int, R: int):
    """Chunked batched scan: trip count = the batch's max chunk count
    (shorter lanes' excess steps are masked — valid=False leaves state and
    outputs exactly as the unbatched skip does).  ``step`` takes and
    returns the lane-major 2D form (:func:`kernels.batched_step.lanes_2d`);
    outputs go back to [B] vectors, the two flags to bool."""

    def batch_run(sp, txns: TxnArrays, nt: BatchNodeTables, n_chunks):
        B = n_chunks.shape[0]
        sp = lanes_2d(sp)
        rows = _node_rows(nt)
        state = (
            jnp.zeros((B, n_planes), jnp.int32),
            tuple(jnp.zeros((B, R), jnp.int32) for _ in range(3)),
        )

        def scan_step(s, x):
            s, out = step(sp, s, lanes_2d(x))
            out = StepOut(*(o[:, 0] for o in out))
            return s, out._replace(conflict=out.conflict != 0,
                                   failed=out.failed != 0)

        def chunk_body(c, carry):
            st, buf = carry
            off = c * CHUNK
            sl = lambda a: jax.lax.dynamic_slice_in_dim(a, off, CHUNK, 0)
            tx = jax.tree_util.tree_map(sl, txns)
            xs = (tx, _gather_node_rows(rows, tx.node))
            st, outs = jax.lax.scan(scan_step, st, xs)
            buf = jax.tree_util.tree_map(
                lambda b, o: jax.lax.dynamic_update_slice_in_dim(b, o, off, 0),
                buf, outs,
            )
            return st, buf

        _, buf = jax.lax.fori_loop(
            0, jnp.max(n_chunks), chunk_body,
            (state, _zero_out_tm(capacity, B)),
        )
        return buf  # StepOut, time-major [capacity, B]

    return batch_run


@functools.lru_cache(maxsize=None)
def _build_batched_fn(sig: tuple, capacity: int, fixed: tuple,
                      n_shards: int, per_shard: int,
                      backend: str = "xla"):
    rows, cols, dies, planes_per_die, _ = sig
    lay = sweep_layout_geom(rows, cols)
    n_planes = rows * cols * dies * planes_per_die
    step = _make_batched_static_step(lay, n_planes, fixed)
    if backend != "xla":
        # lane-tiled Pallas wrapper around the SAME step closure: the
        # kernel body is the step itself, so the pallas path is bit-exact
        # by construction (and pinned so by tests/test_batched_pallas.py)
        step = lane_tiled_step(step, interpret=(backend != "pallas"))
    brun = _make_batched_run(step, capacity, n_planes, lay.R_pad)

    if n_shards > 1:
        spec = (P("lanes"), P(None, "lanes"), P("lanes"), P("lanes"))
        fn = jax.shard_map(brun, mesh=_lane_mesh(n_shards), in_specs=spec,
                           out_specs=P(None, "lanes"), check_vma=False)
    else:
        fn = brun
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# gather-free batched SCOUT runner (venice-family lanes)
#
# The batched static runner above left the paper's own designs on the flat
# per-lane scan: the scout DFS (a while_loop whose trip count diverges per
# lane) was the blocker.  The batched formulation here steps [B] scout DFS
# machines in lockstep — the per-step decision is ``kernels.scout_step``'s
# one-hot compare-and-reduce math (the [B,N]x[N,4] port-table matmul,
# lane-aligned busy/tried bitmaps), the backtracking memory is
# ``kernels.scout_step.walk_lanes``'s iota-select stacks, and each lane
# routes against its OWN link-occupancy map (one [B, L0] busy row per lane
# — the lanes are independent simulations, not one mesh).  Divergence cost
# is max-over-B steps per walk; every decision, rng draw, retry schedule
# and k-scout race stays bit-exact vs the flat scan (pinned in
# tests/test_batched_scout.py against both ``simulate`` and
# ``scalar_ref``).  ``backend`` runs each whole walk inside the Pallas walk
# kernel, one call per walk and lane tile (compiled on GPU/TPU, interpret
# on CPU), and ``xla`` runs the same walk as XLA ops — same math, so
# bit-exact by construction.
# ---------------------------------------------------------------------------


def _avail1_b(res, i, e, d):
    """Batched ``_avail1``: per-lane resource index ``i`` [B] into a
    [B, K] triple, gather-free (one-hot take)."""
    free, gap_s, gap_e = res
    return _gap_avail(onehot.take(gap_s, i), onehot.take(gap_e, i),
                      onehot.take(free, i), e, d)


def _commit1_b(res, i, s, e2, enable):
    """Batched ``_commit1``: one-hot scatter of the per-lane commit."""
    free, gap_s, gap_e = res
    gs, ge, fa = _gap_commit(onehot.take(gap_s, i), onehot.take(gap_e, i),
                             onehot.take(free, i), s, e2)
    upd = onehot.onehot(i, free.shape[1]) & enable[:, None]
    return (
        jnp.where(upd, fa[:, None], free),
        jnp.where(upd, gs[:, None], gap_s),
        jnp.where(upd, ge[:, None], gap_e),
    )


def _sched_gap_b(res, i, e, d, enable):
    s = _avail1_b(res, i, e, d)
    s = jnp.where(enable, s, e)
    res = _commit1_b(res, i, s, s + d, enable)
    return s, res


class ScoutBatchScalars(NamedTuple):
    """Per-lane design scalars of a batched scout group (same layout
    contract as :class:`BatchScalars`) plus the FC node map the scout
    source lookup needs."""

    hold: jnp.ndarray
    allow_nonmin: jnp.ndarray
    n_scouts: jnp.ndarray
    fc_nearest: jnp.ndarray
    count_bus: jnp.ndarray
    ovh: jnp.ndarray
    cmd_base_ns: jnp.ndarray
    xfer_num: jnp.ndarray
    xfer_den: jnp.ndarray
    hop_ns: jnp.ndarray
    d_est_hops: jnp.ndarray
    d_est_pad: jnp.ndarray
    fc_valid: jnp.ndarray  # bool [B, F_pad]
    fc_node: jnp.ndarray  # int32 [B, F_pad]
    res_dead: jnp.ndarray  # bool [B, R_pad]


class ScoutBatchTxnTables(NamedTuple):
    """Per-transaction pre-gathered tables for the scout step, time-major
    (see ``designs.pregather_scout_tables``) — the scout path only ever
    indexes ``dist`` by the transaction's node."""

    dist: jnp.ndarray  # int32 [cap, B, F_pad]


# The largest walk tile the batched scout runner fills with retries
# (PERF.md section 6: the walk kernel's cost per loop iteration by tile)
_TRIES_TILE = 16


def try_slots(B: int, k_max: int, link_w: int, n_nodes: int) -> int:
    """Tries the batched scout runner walks in one call: as many as one
    walk tile of at most ``_TRIES_TILE`` scouts, whose blocks fit
    ``VMEM_BUDGET``, holds at ``k_max`` scouts a try; at least one a lane."""
    return max(B, walk_tile(_TRIES_TILE, link_w, n_nodes) // k_max)


def _try_times(links3, t0):
    """Each lane's retry schedule, int32 [B, _MAX_TRIES], from its links
    triple [B, L] alone: try 0 at ``t0``; try i+1 at the next potential
    link-state change after try i (the next distinct free_at or idle gap
    start above it, one tick later each once none is left); the last try
    once every link is free — the flat ``scout_until_success``'s rule."""
    free, gap_s, _ = links3
    v = jnp.concatenate([free, gap_s], axis=1)  # [B, V]
    V = v.shape[1]
    up = v > t0[:, None]
    earlier = np.arange(V)[None, :] < np.arange(V)[:, None]
    dup = jnp.any((v[:, :, None] == v[:, None, :]) & up[:, None, :]
                  & earlier, axis=2)
    first = up & ~dup  # one entry per distinct event above t0
    below = jnp.sum((v[:, None, :] < v[:, :, None]) & first[:, None, :],
                    axis=2)  # distinct events below each entry
    n_ev = jnp.sum(first, axis=1)
    i = np.arange(1, _MAX_TRIES - 1)  # try i waits for the i-th event
    ev = jnp.min(jnp.where(
        first[:, None, :] & (below[:, None, :] == i[None, :, None] - 1),
        v[:, None, :], _BIG), axis=2)
    ev = jnp.where(i <= n_ev[:, None], ev,
                   _BIG + i - 1 - n_ev[:, None])
    return jnp.concatenate(
        [t0[:, None], ev, jnp.max(free, axis=1)[:, None]], axis=1)


def _rng_advances(rng, n: int):
    """uint32 [B, n + 1]: a lane's scout rng after 0 .. n advances
    ``x -> (x * 747796405 + 2891336453) | 1``, in one vector op: from the
    first advance on the state is odd, and on odd states the advance is
    the affine ``x * a + (c + 1)``, whose powers are constants mod 2^32."""
    a, c = 747796405, 2891336453
    first = (rng * jnp.uint32(a) + jnp.uint32(c)) | jnp.uint32(1)
    mul, add = [1], [0]
    for _ in range(n - 1):
        mul.append(mul[-1] * a % 2**32)
        add.append((add[-1] * a + c + 1) % 2**32)
    later = (first[:, None] * jnp.asarray(mul, jnp.uint32)
             + jnp.asarray(add, jnp.uint32))
    return jnp.concatenate([rng[:, None], later], axis=1)


def _make_batched_scout_step(lay, topo, scout_hop_ns: int, n_planes: int,
                             k_max: int, fixed: tuple, backend: str,
                             slots: int | None = None):
    """The scout-routed scan step over a lane batch [B].

    Mirrors ``scout_step`` + ``scout_until_success`` in ``_make_step``
    operation for operation with a leading lane axis (consult those for
    the modeling semantics); all arithmetic is int32 one-hot/masked-select
    work, so batched == unbatched bit-for-bit.  The flat ``scout_route``
    DFS is replaced by ``kernels.ops.route_dfs``: the batched ``step_math``
    decision and its stacks, walked in the Pallas walk kernel or as XLA
    ops — the same Algorithm-1 decision procedure, pinned equivalent.
    ``slots`` fixes the tries walked per call, at least one a lane
    (tests); None takes :func:`try_slots` of the batch.  Returns ``(state', (StepOut,
    dfs))``: ``dfs`` is, per lane, this step's DFS loop iterations in its
    walk tiles, the steps its own walks took, the tries it walked and the
    retry loop's rounds (see ``scout_until_success_b``).
    """
    L0 = lay.L_pad
    n_fcs = lay.rows
    n_nodes = lay.n_nodes
    width = link_pad(L0)
    fixed = dict(zip(_PROMOTABLE, fixed))
    tables_dev = jnp.asarray(pack_tables(topo))
    cols = topo.cols

    def fx(sp, name):
        v = fixed[name]
        return getattr(sp, name) if v is None else v

    def cmd_ticks(sp, hops):
        ns = fx(sp, "cmd_base_ns") + hops * fx(sp, "hop_ns")
        return jnp.maximum(_ceil_div(ns, TICK_NS), 1).astype(jnp.int32)

    def xfer_ticks(sp, nbytes, hops):
        ns = _ceil_div(nbytes * fx(sp, "xfer_num"), fx(sp, "xfer_den"))
        ns = ns + hops * fx(sp, "hop_ns")
        return _ceil_div(ns, TICK_NS).astype(jnp.int32)

    def commit_mask_b(res, mask, s, e2, enable):
        free, gap_s, gap_e = res
        gs, ge, fa = _gap_commit(gap_s, gap_e, free, s[:, None], e2[:, None])
        take = mask & enable[:, None]
        return (
            jnp.where(take, fa, free),
            jnp.where(take, gs, gap_s),
            jnp.where(take, ge, gap_e),
        )

    def _merge_b(take, a, b):
        return jax.tree_util.tree_map(
            lambda x, y: jnp.where(
                take.reshape(take.shape + (1,) * (x.ndim - 1)), x, y),
            a, b,
        )

    def scout_until_success_b(links3, sp, src, dst, t0, rng, d_hold, valid):
        """Batched ``scout_until_success``: every lane follows its own
        retry schedule (its links triple is lane-local), bit-identical to
        the flat loop.  The schedule does not depend on the walks
        (:func:`_try_times`; ``links3`` is committed only after the loop),
        so each round of the loop walks the next R tries of each of its L
        live lanes side by side, in one ``route_dfs`` call of S x k_max
        scouts — S try slots (:func:`try_slots`, or ``slots``), R = S // L
        — and keeps each lane's first success, or its last try, exactly as
        the sequential loop leaves them; every round walks the same tile,
        so fewer live lanes walk more tries each.  Unfilled slots walk
        src==dst==0 dummies; the first round always runs.  Also returns,
        per lane: the DFS loop's iterations of every call (each of its
        walk tiles once), the steps of the walks the sequential schedule
        takes (every raced scout of every try up to the first success),
        the tries walked, speculative ones included, and the rounds."""
        n_scouts = fx(sp, "n_scouts")
        dead_links = sp.res_dead[:, :L0]
        B = src.shape[0]
        allow = fx(sp, "allow_nonmin")  # promoted-static or per-lane
        S = max(B, slots or try_slots(B, k_max, width, n_nodes))
        b_tile = (walk_tile(S * k_max, width, n_nodes) if backend != "xla"
                  else S * k_max)
        tile_first = np.arange(S * k_max) % b_tile == 0
        s_iota = np.arange(S)
        # scouts raced per try, and the rng after every advance of the
        # step's tries (a try advances each raced scout's rng once)
        n_act = jnp.broadcast_to(
            jnp.minimum(jnp.asarray(n_scouts, jnp.int32), k_max), (B,))
        rngs = _rng_advances(rng, _MAX_TRIES * k_max)
        sched = _try_times(links3, t0)  # [B, _MAX_TRIES]

        def gather(sel, x):  # x[lane of slot] for each slot: [S, ...]
            m = sel.reshape(sel.shape + (1,) * (x.ndim - 1))
            if x.dtype == jnp.bool_:
                return jnp.any(m & x[None], axis=1)
            return jnp.sum(jnp.where(m, x[None], 0), axis=1).astype(x.dtype)

        def round_(carry):
            res, tries_, (trips, own, walked), rounds = carry
            live = valid & (~res.success) & (tries_ < _MAX_TRIES)
            n_live = jnp.sum(live.astype(jnp.int32))
            j = jnp.max(jnp.where(live, tries_, 0))  # the same on live lanes
            R = jnp.minimum(S // jnp.maximum(n_live, 1), _MAX_TRIES - j)
            # slot s holds try j + s % R of the (s // R)-th live lane
            pos = jnp.cumsum(live.astype(jnp.int32)) - 1
            sel = live[None, :] & (pos[None, :] == (s_iota // R)[:, None])
            filled = jnp.any(sel, axis=1)
            idx = jnp.minimum(j + s_iota % R, _MAX_TRIES - 1)
            t_s = onehot.take(gather(sel, sched), idx)
            busy = _busy_at(tuple(gather(sel, a) for a in links3),
                            t_s[:, None], gather(sel, d_hold)[:, None])
            busy = busy | gather(sel, dead_links)
            na_s = gather(sel, n_act)
            rng_s = gather(sel, rngs)
            act = [filled & (k < na_s) for k in range(k_max)]
            seeds = [onehot.take(rng_s, idx * na_s + k + 1)
                     for k in range(k_max)]
            rows = lambda x: jnp.stack(x, axis=1).reshape(
                (S * k_max,) + x[0].shape[1:])
            src_s, dst_s = gather(sel, src), gather(sel, dst)
            out = route_dfs(
                tables_dev, rows([jnp.where(a, src_s, 0) for a in act]),
                rows([jnp.where(a, dst_s, 0) for a in act]),
                jnp.repeat(busy, k_max, axis=0), rows(seeds),
                allow if isinstance(allow, (bool, np.bool_))
                else jnp.repeat(gather(sel, jnp.asarray(allow)), k_max),
                cols=cols, n_nodes=n_nodes, use_pallas=backend != "xla",
                interpret=backend != "pallas")
            # every lane waits on every walk tile of the call
            trips = trips + jnp.sum(jnp.where(tile_first, out.tile_steps, 0))
            out = out._replace(path_mask=out.path_mask[:, :L0])
            out = jax.tree_util.tree_map(
                lambda x: x.reshape((S, k_max) + x.shape[1:]), out)
            # each try raced its scouts: the fewest-hop success won
            best = None
            steps = jnp.zeros((S,), jnp.int32)
            for k in range(k_max):
                rk = jax.tree_util.tree_map(lambda x: x[:, k], out)
                steps = steps + jnp.where(act[k], rk.steps, 0)
                if best is None:
                    best = rk
                else:
                    win = rk.success & act[k] & (
                        (~best.success) | (rk.hops < best.hops))
                    best = _merge_b(win, rk, best)
            # each live lane keeps its first success, else its last try:
            # the tries the sequential loop would have taken
            mine = sel.T  # [B, S]
            first = jnp.min(jnp.where(mine & best.success, s_iota, S), axis=1)
            last = jnp.max(jnp.where(mine, s_iota, -1), axis=1)
            kept = jnp.where(first < S, first, last)
            pick = onehot.onehot(kept, S)
            res = _merge_b(live, jax.tree_util.tree_map(
                lambda x: gather(pick, x), best), res)
            taken = mine & (s_iota <= kept[:, None])
            own = own + jnp.sum(jnp.where(taken, steps, 0), axis=1)
            tries_ = jnp.where(live, j + kept - pos * R + 1, tries_)
            walked = walked + jnp.where(live, R, 0)
            return res, tries_, (trips, own, walked), rounds + 1

        def cond(carry):
            res, tries_, _, rounds = carry
            return (rounds == 0) | jnp.any(
                valid & (~res.success) & (tries_ < _MAX_TRIES))

        zero = jnp.zeros((B,), jnp.int32)
        res0 = BatchRouteOut(
            success=jnp.zeros((B,), bool),
            path_mask=jnp.zeros((B, L0), bool),
            hops=zero, steps=zero, misroutes=zero, tile_steps=zero)
        res, tries_, dfs, rounds = jax.lax.while_loop(
            cond, round_, (res0, zero, (zero,) * 3, jnp.int32(0)))
        # the last try's time and the rng after it (none: t0, rng as given)
        t = jnp.where(tries_ > 0,
                      onehot.take(sched, jnp.maximum(tries_ - 1, 0)), t0)
        rng = onehot.take(rngs, tries_ * n_act)
        return res, t, rng, tries_, dfs + (jnp.broadcast_to(rounds, (B,)),)

    def step(sp: ScoutBatchScalars, state, xs):
        tx, tt = xs
        plane_free, links, fcs, chips, rng = state
        valid = tx.valid
        is_read = tx.kind == KIND_READ
        tcand = jnp.maximum(tx.arrival, onehot.take(plane_free, tx.plane))
        hold = fx(sp, "hold")

        d_est = (xfer_ticks(sp, tx.nbytes, fx(sp, "d_est_hops"))
                 + fx(sp, "d_est_pad"))
        if hold is not False:
            d_est = d_est + jnp.where(
                jnp.logical_and(hold, is_read), tx.op_ticks, 0
            )
        avail = _avail_all(fcs, tcand[:, None], d_est[:, None])
        avail = jnp.where(sp.fc_valid[:, :n_fcs], avail, _BIG)
        dist_row = tt.dist[:, :n_fcs]
        free_now = avail <= tcand[:, None]
        any_free = jnp.any(free_now, axis=1)
        by_dist = onehot.argmin(jnp.where(free_now, dist_row, _BIG))
        by_time = onehot.argmin(avail)
        fc = jnp.where(any_free, by_dist, by_time)
        t0 = jnp.maximum(tcand, onehot.take(avail, fc))
        src = onehot.take(sp.fc_node[:, :n_fcs], fc)
        min_hops = onehot.take(dist_row, fc)
        cmd_pkt = cmd_ticks(sp, min_hops)
        en_cmd = valid & is_read & jnp.logical_not(hold)
        s_cmd, fcs = _sched_gap_b(fcs, fc, t0, cmd_pkt, en_cmd)
        ready_r = s_cmd + cmd_pkt + tx.op_ticks
        t_nonread = jnp.maximum(t0, _avail1_b(chips, tx.node, t0, d_est))
        t_read = jnp.maximum(
            jnp.maximum(ready_r, _avail1_b(fcs, fc, ready_r, d_est)),
            _avail1_b(chips, tx.node, ready_r, d_est),
        )
        t_xfer_req = jnp.where(is_read, t_read, t_nonread)
        t_scout = jnp.where(hold, t0, t_xfer_req)
        sres, t_resv, rng, tries, dfs = scout_until_success_b(
            links, sp, src, tx.node, t_scout, rng, d_est, valid
        )
        hops_o = sres.hops
        rtt = _ceil_div((sres.steps + hops_o) * scout_hop_ns, TICK_NS)
        start = t_resv + rtt.astype(jnp.int32)
        cmd_v = cmd_ticks(sp, hops_o)
        xfer_v = xfer_ticks(sp, tx.nbytes, hops_o)
        dur_p = jnp.where(is_read, xfer_v, cmd_v + xfer_v)
        end_p = start + dur_p
        done_p = jnp.where(is_read, end_p, end_p + tx.op_ticks)
        wait_p = (s_cmd - t0) + (start - t_xfer_req)
        done_r_h = start + cmd_v + tx.op_ticks + xfer_v
        data_end_w = start + cmd_v + xfer_v
        circuit_end = jnp.where(is_read, done_r_h, data_end_w)
        done_h = jnp.where(is_read, done_r_h, data_end_w + tx.op_ticks)
        commit_end = jnp.where(hold, circuit_end, end_p)
        done = jnp.where(hold, done_h, done_p)
        wait = jnp.where(hold, start - t0, wait_p)
        fail = ~sres.success
        ok = valid & sres.success
        done = jnp.where(fail, tcand + FAIL_TIMEOUT, done)
        wait = jnp.where(fail, FAIL_TIMEOUT, wait)
        links = commit_mask_b(links, sres.path_mask, t_resv, commit_end, ok)
        fcs = _commit1_b(fcs, fc, t_resv, commit_end, ok)
        chips = _commit1_b(chips, tx.node, t_resv, commit_end, ok)
        upd = onehot.onehot(tx.plane, n_planes) & valid[:, None]
        plane_free = jnp.where(upd, done[:, None], plane_free)
        zero = jnp.zeros_like(done)
        out = StepOut(
            completion=jnp.where(valid, done, tx.arrival),
            wait=jnp.where(valid, wait, 0),
            conflict=valid & ((tries > 1) | fail),
            hops=jnp.where(valid, hops_o, 0),
            tries=jnp.where(valid, tries, 0),
            scout_steps=jnp.where(valid, sres.steps, 0),
            misroutes=jnp.where(valid, sres.misroutes, 0),
            bus_hold=zero,
            link_hold=jnp.where(valid & jnp.logical_not(fail),
                                hops_o * (commit_end - t_resv), 0),
            failed=valid & fail,
        )
        return (plane_free, links, fcs, chips, rng), (out, dfs)

    return step


def _make_batched_scout_run(step, capacity: int, n_planes: int, L0: int,
                            n_fcs: int, n_nodes: int):
    """Chunked batched scout scan — the scout-state analogue of
    :func:`_make_batched_run` (seeds ride as an argument; the scan state
    mirrors the flat scout ``init_state`` with a leading lane axis).
    Returns the time-major ``StepOut`` and the run's DFS counts, int32
    [B, 4]: the DFS loop's iterations in each lane's walk tiles, the
    iterations of each lane's own walks, the tries it walked and the
    retry loop's rounds (the same on every lane of a shard)."""

    def batch_run(scal, seeds, txns: TxnArrays, tt: ScoutBatchTxnTables,
                  n_chunks):
        B = n_chunks.shape[0]
        trip = lambda n: tuple(
            jnp.zeros((B, n), jnp.int32) for _ in range(3))
        state = (
            jnp.zeros((B, n_planes), jnp.int32),
            trip(L0),
            trip(n_fcs),
            trip(n_nodes),
            jnp.asarray(seeds, jnp.uint32),
        )

        def scan_step(carry, x):
            st, dfs = carry
            st, (out, d) = step(scal, st, x)
            return (st, tuple(a + b for a, b in zip(dfs, d))), out

        def chunk_body(c, carry):
            st, dfs, buf = carry
            off = c * CHUNK
            sl = lambda a: jax.lax.dynamic_slice_in_dim(a, off, CHUNK, 0)
            xs = (jax.tree_util.tree_map(sl, txns),
                  jax.tree_util.tree_map(sl, tt))
            (st, dfs), outs = jax.lax.scan(scan_step, (st, dfs), xs)
            buf = jax.tree_util.tree_map(
                lambda b, o: jax.lax.dynamic_update_slice_in_dim(b, o, off, 0),
                buf, outs,
            )
            return st, dfs, buf

        dfs0 = (jnp.zeros((B,), jnp.int32),) * 4
        _, dfs, buf = jax.lax.fori_loop(
            0, jnp.max(n_chunks), chunk_body,
            (state, dfs0, _zero_out_tm(capacity, B)),
        )
        # StepOut, time-major [capacity, B]; the DFS counts [B, 4]
        return buf, jnp.stack(dfs, axis=1)

    return batch_run


@functools.lru_cache(maxsize=None)
def _build_batched_scout_fn(sig: tuple, capacity: int, k_max: int,
                            fixed: tuple, n_shards: int, per_shard: int,
                            backend: str = "xla"):
    rows, cols, dies, planes_per_die, scout_hop_ns = sig
    lay = sweep_layout_geom(rows, cols)
    topo = build_mesh(rows, cols)
    n_planes = rows * cols * dies * planes_per_die
    step = _make_batched_scout_step(lay, topo, scout_hop_ns, n_planes,
                                    k_max, fixed, backend)
    brun = _make_batched_scout_run(step, capacity, n_planes, lay.L_pad,
                                   lay.rows, lay.n_nodes)

    if n_shards > 1:
        spec = (P("lanes"), P("lanes"), P(None, "lanes"), P(None, "lanes"),
                P("lanes"))
        fn = jax.shard_map(brun, mesh=_lane_mesh(n_shards), in_specs=spec,
                           out_specs=(P(None, "lanes"), P("lanes")),
                           check_vma=False)
    else:
        fn = brun
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# executables: logical keys, shape avatars, compile
#
# Every program variant has a *logical key* — everything its machine code
# depends on besides the source (geometry sig, capacity bucket, lane
# layout, cost class, promotions, shard count, and for the batched
# variants the lane-step backend, always last).  A key resolves through
# the in-process ``_EXEC_CACHE``, else a fresh compile (JAX's persistent
# compilation cache serves repeats across processes).  Compilation
# happens from ShapeDtypeStruct avatars, so the sweep planner can compile
# executables on a background thread before the group's data is even
# stacked (the overlapped compile/execute pipeline in ``sweep_plan``).
# ---------------------------------------------------------------------------

_EXEC_CACHE: dict = {}
_TALLY_LOCK = threading.Lock()


def clear_exec_cache() -> None:
    """Drop in-process compiled executables (tests)."""
    _EXEC_CACHE.clear()


def lane_group_key(sig, capacity, G, k_max, has_scout, fixed, n_shards):
    return ("lane", sig, capacity, G, k_max, has_scout, fixed, n_shards)


def lanec_group_key(sig, capacity, G, k_max, has_scout, fixed, n_shards):
    """State-carrying lane group (the streaming engine's windows)."""
    return ("lanec", sig, capacity, G, k_max, has_scout, fixed, n_shards)


def stack_group_key(sig, capacity, K, k_max, has_scout, fixed, n_shards):
    return ("stack", sig, capacity, K, k_max, has_scout, fixed, n_shards)


def batched_group_key(sig, capacity, per_shard, fixed, n_shards,
                      backend: str = "xla"):
    return ("batched", sig, capacity, per_shard, fixed, n_shards, backend)


def bscout_group_key(sig, capacity, per_shard, k_max, fixed, n_shards,
                     backend: str = "xla"):
    """Batched scout group."""
    return ("bscout", sig, capacity, per_shard, k_max, fixed, n_shards,
            backend)


def kernel_backend_of_key(key: tuple) -> str:
    """Which lane-step kernel a group key dispatches to: "xla" for all
    unbatched variants, else the batched key's backend, with "pallas"
    reported as "pallas-compiled"."""
    if key[0] not in ("batched", "bscout"):
        return "xla"
    return "pallas-compiled" if key[-1] == "pallas" else key[-1]


_TABLE_SCALAR_DTYPES = dict(
    is_scout=bool, fc_nearest=bool, ovh=np.int32, cmd_base_ns=np.int32,
    xfer_num=np.int32, xfer_den=np.int32, hop_ns=np.int32,
    allow_nonmin=bool, hold=bool, n_scouts=np.int32, d_est_hops=np.int32,
    d_est_pad=np.int32, count_bus=bool,
)


def _sds(shape, dtype, spec, n_shards):
    if n_shards <= 1:
        return jax.ShapeDtypeStruct(shape, dtype)
    return jax.ShapeDtypeStruct(
        shape, dtype, sharding=NamedSharding(_lane_mesh(n_shards), spec)
    )


def _tables_avatar(lay, G: int, n_shards: int) -> LaneTables:
    L = P("lanes")
    F0, N, R = lay.F_pad, lay.n_nodes, lay.R_pad
    f = {name: _sds((G,), dt, L, n_shards)
         for name, dt in _TABLE_SCALAR_DTYPES.items()}
    f.update(
        cmask=_sds((G, F0, N, 2, R), bool, L, n_shards),
        hops=_sds((G, F0, N, 2), np.int32, L, n_shards),
        cand2_ok=_sds((G, N), bool, L, n_shards),
        fc_fixed=_sds((G, N, 2), np.int32, L, n_shards),
        dist=_sds((G, F0, N), np.int32, L, n_shards),
        fc_valid=_sds((G, F0), bool, L, n_shards),
        fc_node=_sds((G, F0), np.int32, L, n_shards),
        res_dead=_sds((G, R), bool, L, n_shards),
    )
    return LaneTables(**f)


def _txns_avatar(G: int, capacity: int, n_shards: int,
                 time_major: bool = False) -> TxnArrays:
    shape = (capacity, G) if time_major else (G, capacity)
    spec = P(None, "lanes") if time_major else P("lanes")
    mk = lambda dt: _sds(shape, dt, spec, n_shards)
    return TxnArrays(
        arrival=mk(np.int32), kind=mk(np.int32), plane=mk(np.int32),
        node=mk(np.int32), row=mk(np.int32), nbytes=mk(np.int32),
        op_ticks=mk(np.int32), valid=mk(bool),
    )


def _state_avatar(sig, G: int, has_scout: bool, n_shards: int):
    """Shape avatar of the carried scan state (mirrors ``init_state`` in
    ``_make_step``, with a leading lane axis)."""
    rows, cols, dies, planes_per_die, _ = sig
    n_planes = rows * cols * dies * planes_per_die
    lay = sweep_layout_geom(rows, cols)
    L = P("lanes")
    mk = lambda n: _sds((G, n), np.int32, L, n_shards)
    trip = lambda n: (mk(n), mk(n), mk(n))
    if not has_scout:
        return (mk(n_planes), trip(lay.R_pad))
    return (
        mk(n_planes),
        trip(lay.L_pad),
        trip(lay.rows),
        trip(lay.n_nodes),
        _sds((G,), np.uint32, L, n_shards),
    )


def _avatars_for_key(key: tuple):
    kind = key[0]
    if kind in ("lane", "stack", "lanec"):
        _, sig, capacity, n, k_max, has_scout, fixed, n_shards = key
        G = n * n_shards if kind == "stack" else n
        lay = sweep_layout_geom(sig[0], sig[1])
        second = (
            _state_avatar(sig, G, has_scout, n_shards)
            if kind == "lanec"
            else _sds((G,), np.uint32, P("lanes"), n_shards)
        )
        return (
            _tables_avatar(lay, G, n_shards),
            second,
            _txns_avatar(G, capacity, n_shards),
            _sds((G,), np.int32, P("lanes"), n_shards),
        )
    if kind == "bscout":
        _, sig, capacity, per_shard, k_max, fixed, n_shards, _ = key
        B = per_shard * n_shards
        lay = sweep_layout_geom(sig[0], sig[1])
        F0, R = lay.F_pad, lay.R_pad
        L, T = P("lanes"), P(None, "lanes")
        scal = ScoutBatchScalars(
            *(_sds((B,), _TABLE_SCALAR_DTYPES[name], L, n_shards)
              for name in _PROMOTABLE),
            fc_valid=_sds((B, F0), bool, L, n_shards),
            fc_node=_sds((B, F0), np.int32, L, n_shards),
            res_dead=_sds((B, R), bool, L, n_shards),
        )
        return (
            scal,
            _sds((B,), np.uint32, L, n_shards),
            _txns_avatar(B, capacity, n_shards, time_major=True),
            ScoutBatchTxnTables(
                dist=_sds((capacity, B, F0), np.int32, T, n_shards)),
            _sds((B,), np.int32, L, n_shards),
        )
    _, sig, capacity, per_shard, fixed, n_shards, _ = key
    B = per_shard * n_shards
    lay = sweep_layout_geom(sig[0], sig[1])
    F0, R, N = lay.F_pad, lay.R_pad, lay.n_nodes
    W = mask_words_per_row(R)
    L = P("lanes")
    scal = BatchScalars(
        *(_sds((B,), _TABLE_SCALAR_DTYPES[name], L, n_shards)
          for name in _PROMOTABLE),
        fc_valid=_sds((B, F0), bool, L, n_shards),
        res_dead=_sds((B, R), bool, L, n_shards),
    )
    nt = BatchNodeTables(
        mask_words=_sds((B, N, F0, 2, W), np.int32, L, n_shards),
        hops=_sds((B, N, F0, 2), np.int32, L, n_shards),
        dist=_sds((B, N, F0), np.int32, L, n_shards),
        cand2=_sds((B, N), bool, L, n_shards),
        fc_fixed=_sds((B, N, 2), np.int32, L, n_shards),
    )
    return (
        scal,
        _txns_avatar(B, capacity, n_shards, time_major=True),
        nt,
        _sds((B,), np.int32, L, n_shards),
    )


def _fn_for_key(key: tuple):
    kind = key[0]
    if kind == "lane":
        _, sig, capacity, G, k_max, has_scout, fixed, n_shards = key
        return _build_group_fn(sig, capacity, k_max, has_scout, fixed,
                               n_shards)
    if kind == "lanec":
        _, sig, capacity, G, k_max, has_scout, fixed, n_shards = key
        return _build_group_fn_carry(sig, capacity, k_max, has_scout, fixed,
                                     n_shards)
    if kind == "stack":
        _, sig, capacity, K, k_max, has_scout, fixed, n_shards = key
        return _build_stack_fn(sig, capacity, K, k_max, has_scout, fixed,
                               n_shards)
    if kind == "bscout":
        _, sig, capacity, per_shard, k_max, fixed, n_shards, backend = key
        return _build_batched_scout_fn(sig, capacity, k_max, fixed,
                                       n_shards, per_shard, backend)
    _, sig, capacity, per_shard, fixed, n_shards, backend = key
    return _build_batched_fn(sig, capacity, fixed, n_shards, per_shard,
                             backend)


def lower_for_key(key: tuple):
    """Trace + lower the program for ``key`` (no backend compile).

    Tracing/lowering is Python-heavy (GIL-bound), so the overlapped
    pipeline runs it on the MAIN thread during planning; the XLA backend
    compile (``.compile()``, releases the GIL) is what goes to the worker
    threads.  Returns None when the executable is already in the
    in-process cache."""
    if key in _EXEC_CACHE:
        return None
    return _fn_for_key(key).lower(*_avatars_for_key(key))


def ensure_compiled(key: tuple, lowered=None):
    """Resolve ``key`` to a loaded executable: the in-process cache, else
    compile.

    Returns ``(compiled, seconds, source)`` with source ``"mem"`` or
    ``"build"`` — ``seconds`` is the compile wall-clock (0 for "mem").
    Thread-safe for distinct keys (the overlapped pipeline compiles on
    worker threads); ``lowered`` is the optional pre-traced module from
    :func:`lower_for_key`.
    """
    hit = _EXEC_CACHE.get(key)
    if hit is not None:
        return hit, 0.0, "mem"
    from repro.ssd import bench

    if lowered is None:
        lowered = _fn_for_key(key).lower(*_avatars_for_key(key))
    t0 = time.perf_counter()
    compiled = lowered.compile()  # JAX's persistent cache serves repeats
    dt = time.perf_counter() - t0
    # tallied here, not from dispatched groups: background compiles kicked
    # off by ``sweep_plan.precompile`` count even when they finish before
    # any group adopts them (the lock: compile-pool workers tally
    # concurrently)
    with _TALLY_LOCK:
        bench.PERF["compile_s"] += dt
    tr = obs_spans.TRACER
    if tr is not None:
        tr.complete("compile", f"compile:{key[0]}", tr.now_us() - dt * 1e6,
                    dt * 1e6, {"source": "build"})
    _EXEC_CACHE[key] = compiled
    return compiled, dt, "build"


def _put_args(args, specs, n_shards: int):
    if n_shards <= 1:
        return jax.tree_util.tree_map(jnp.asarray, args)
    mesh = _lane_mesh(n_shards)
    return tuple(
        jax.tree_util.tree_map(
            lambda a: jax.device_put(a, NamedSharding(mesh, spec)), arg
        )
        for arg, spec in zip(args, specs)
    )


def _run_compiled(key: tuple, args: tuple, specs: tuple, *, lanes: int,
                  capacity: int, n_shards: int, has_scout: bool,
                  steps: int, t_pack: float | None = None,
                  aux: bool = False) -> tuple:
    """Shared execute-and-report body of the group runners: resolve the
    executable, place the arguments, dispatch, and record the per-group
    attribution (variant/cache source/compile-exec split; ``steps``
    is the executed-step count incl. padding waste; ``put_bytes`` the
    bytes placed on the devices).

    ``t_pack`` is when the caller began packing the arguments
    (``time.perf_counter``): packing ends here.  The put only enqueues
    the copies to the devices (``put_s``); the copy itself, the run until
    the outputs are ready, and the fetch of the outputs to the host make
    up ``exec_s``.  ``aux``: the executable returns ``(StepOut, extra)``,
    and ``extra`` comes back as ``perf["aux"]`` for the caller to reduce."""
    t_enter = time.perf_counter()
    if t_pack is not None:
        obs_spans.interval("plan", "pack", t_pack, t_enter)
    compiled, dt, src = ensure_compiled(key)
    t_put = time.perf_counter()
    args = _put_args(args, specs, n_shards)
    t0 = time.perf_counter()
    obs_spans.interval("exec", "put", t_put, t0)
    with obs_spans.span("exec", f"exec:{key[0]}", lanes=lanes,
                        shards=n_shards, steps=steps * CHUNK):
        with obs_spans.span("exec", "run"):
            outs = jax.block_until_ready(compiled(*args))
        t_ready = time.perf_counter()
        with obs_spans.span("exec", "fetch"):
            outs = jax.device_get(outs)
        fetch_s = time.perf_counter() - t_ready
    exec_s = time.perf_counter() - t0
    put_s = t0 - t_put
    put_bytes = sum(a.nbytes for a in jax.tree_util.tree_leaves(args))
    kb = kernel_backend_of_key(key)
    perf = {
        "variant": key[0], "lanes": lanes, "capacity": capacity,
        "shards": n_shards, "scout": has_scout,
        "steps": steps * CHUNK, "cache": src,
        "kernel_backend": kb,
        "compile_s": round(dt, 3),
        "exec_s": round(exec_s, 3),
        "put_bytes": put_bytes,
    }
    if aux:
        outs, perf["aux"] = outs
    from repro.ssd import bench

    # kernel-dispatch scoreboard: which backend ran, and how many
    # lane-steps went through the batched step vs the unbatched scan —
    # split per cost class so the scout promotion is attributable
    # (the lock: the streaming engine executes groups off-thread)
    with _TALLY_LOCK:
        bench.PERF["kernel_backends"][kb] = (
            bench.PERF["kernel_backends"].get(kb, 0) + 1)
        if has_scout:
            share_key = ("steps_scout_batched" if key[0] == "bscout"
                         else "steps_scout_unbatched")
        else:
            share_key = ("steps_batched" if key[0] == "batched"
                         else "steps_unbatched")
        bench.PERF[share_key] += steps * CHUNK
        if t_pack is not None:
            bench.PERF["pack_s"] += t_enter - t_pack
        bench.PERF["put_s"] += put_s
        bench.PERF["fetch_s"] += fetch_s
        bench.PERF["put_bytes"] += put_bytes
    return outs, perf


def run_group(sig: tuple, tables, seeds, txns: TxnArrays, n_chunks,
              k_max: int, has_scout: bool, fixed: tuple,
              n_shards: int, K: int = 0,
              t_pack: float | None = None) -> tuple:
    """Execute one lane group; returns (StepOut [G, cap], perf).

    ``tables``/``txns`` carry a leading lane axis [G] (numpy trees);
    ``seeds``/``n_chunks`` are [G] arrays.  ``K == 0``: one unbatched
    lane per shard (G == n_shards); ``K > 0``: the stacked layout, K
    sequential lanes per shard (G == n_shards*K).  ``t_pack``: see
    :func:`_run_compiled`.
    """
    G = int(len(seeds))
    capacity = int(np.asarray(txns.arrival).shape[1])
    ncs = np.asarray(n_chunks, np.int32)
    if K:
        key = stack_group_key(sig, capacity, K, k_max, has_scout, fixed,
                              n_shards)
    else:
        key = lane_group_key(sig, capacity, G, k_max, has_scout, fixed,
                             n_shards)
    return _run_compiled(
        key, (tables, np.asarray(seeds, np.uint32), txns, ncs),
        (P("lanes"),) * 4, lanes=G, capacity=capacity, n_shards=n_shards,
        has_scout=has_scout, steps=int(ncs.sum()), t_pack=t_pack,
    )


def initial_lane_state(cfg: SSDConfig, has_scout: bool, seed: int):
    """Host (numpy) zero scan state for one lane — what ``init_state``
    inside ``_make_step`` builds device-side.  The streaming engine seeds
    window 0 with this, so window 0 of a streamed replay is bit-identical
    to the same prefix under :func:`simulate`."""
    sig = _geom_sig(cfg)
    lay = sweep_layout_geom(sig[0], sig[1])
    z = lambda n: np.zeros((n,), np.int32)
    trip = lambda n: (z(n), z(n), z(n))
    if not has_scout:
        return (z(cfg.n_planes), trip(lay.R_pad))
    return (
        z(cfg.n_planes),
        trip(lay.L_pad),
        trip(lay.rows),
        trip(lay.n_nodes),
        np.uint32(seed),
    )


# floor for rebased timestamps: streamed windows re-inject deferred
# transactions with their original (now negative) frame-shifted arrivals,
# so the rebase must NOT clamp at 0 — but idle windows would otherwise
# drift state toward int32 underflow.  Anything at or below the floor is
# "deep past": the bookkeeping only ever compares such values against
# candidate starts >= arrivals >= the same floor (the streaming engine
# guards deferred arrivals against it), and for those comparisons every
# deep-past value behaves identically.
REBASE_FLOOR = -(1 << 30)


def rebase_lane_state(state, delta_ticks: int):
    """Shift every timestamp in a carried scan state back by ``delta_ticks``
    (the window span) — a pure frame change, floored at ``REBASE_FLOOR``.

    The unclamped shift is what makes window-boundary carry bit-exact: the
    scan's resource bookkeeping (``_gap_avail`` / ``_busy_at`` / commits)
    is purely relative, so state that reads exactly ``monolithic value
    minus the accumulated window spans`` — including negative entries for
    resources still busy from a previous window — reproduces the
    monolithic run's comparisons verbatim, even for deferred transactions
    whose rebased arrivals are themselves negative.  The scout RNG word is
    not a timestamp and rides through untouched."""

    def f(a):
        a = np.asarray(a)
        if a.dtype != np.int32:
            return a  # uint32 rng state
        return np.maximum(a.astype(np.int64) - int(delta_ticks),
                          REBASE_FLOOR).astype(np.int32)

    return jax.tree_util.tree_map(f, state)


def run_group_carry(sig: tuple, tables, state, txns: TxnArrays, n_chunks,
                    k_max: int, has_scout: bool, fixed: tuple,
                    n_shards: int) -> tuple:
    """Execute one state-carrying lane group (streaming window); returns
    ``(state' [G, ...], StepOut [G, cap], perf)``.

    Same layout contract as :func:`run_group`, except the per-lane scan
    state replaces the seeds argument (the scout RNG seed lives inside the
    state) and comes back rebased-ready for the next window."""
    ncs = np.asarray(n_chunks, np.int32)
    G = int(ncs.shape[0])
    capacity = int(np.asarray(txns.arrival).shape[1])
    key = lanec_group_key(sig, capacity, G, k_max, has_scout, fixed,
                          n_shards)
    outs, perf = _run_compiled(
        key, (tables, state, txns, ncs), (P("lanes"),) * 4,
        lanes=G, capacity=capacity, n_shards=n_shards,
        has_scout=has_scout, steps=int(ncs.sum()),
    )
    st, buf = outs
    return st, buf, perf


def run_batched_group(sig: tuple, scal: BatchScalars, txns: TxnArrays,
                      nt: BatchNodeTables, n_chunks, fixed: tuple,
                      n_shards: int, per_shard: int,
                      backend: str = "xla",
                      t_pack: float | None = None) -> tuple:
    """Execute one batched static group; returns (StepOut [cap, B], perf).

    ``txns`` is a time-major numpy tree [cap, B]; ``scal``, the per-lane
    node tables ``nt`` [B, N, ...] and ``n_chunks`` lead with the [B]
    lane axis.  Executed steps are charged at the per-shard max chunk
    count (the masked tail of shorter lanes is the batch's padding waste,
    kept visible in ``steps``).  ``backend`` picks
    the lane-step kernel (a resolved name from
    :func:`resolve_lane_backend`); every backend is bit-exact.
    ``t_pack``: see :func:`_run_compiled`.
    """
    B = int(np.asarray(n_chunks).shape[0])
    capacity = int(np.asarray(txns.arrival).shape[0])
    ncs = np.asarray(n_chunks, np.int32)
    shard_steps = sum(
        int(ncs[s * per_shard:(s + 1) * per_shard].max(initial=0))
        * per_shard for s in range(max(1, n_shards))
    )
    return _run_compiled(
        batched_group_key(sig, capacity, per_shard, fixed, n_shards,
                          backend),
        (scal, txns, nt, ncs),
        (P("lanes"), P(None, "lanes"), P("lanes"), P("lanes")),
        lanes=B, capacity=capacity, n_shards=n_shards, has_scout=False,
        steps=shard_steps, t_pack=t_pack,
    )


def run_batched_scout_group(sig: tuple, scal: ScoutBatchScalars, seeds,
                            txns: TxnArrays, tt: ScoutBatchTxnTables,
                            n_chunks, k_max: int, fixed: tuple,
                            n_shards: int, per_shard: int,
                            backend: str = "xla",
                            t_pack: float | None = None) -> tuple:
    """Execute one batched scout group; returns (StepOut [cap, B], per
    lane the DFS steps of its own walks and the tries it walked [B, 2],
    perf).

    Same layout contract as :func:`run_batched_group` plus the per-lane
    rng ``seeds`` [B] (the scout state's fifth leg) and ``k_max`` (the
    group's raced-scout ceiling — lanes below it are masked per their
    ``n_scouts``).  Every backend is bit-exact.  ``t_pack``: see
    :func:`_run_compiled`.  ``perf``: ``dfs_steps_padded``, the DFS loop's
    iterations in each lane's walk tiles summed over lanes;
    ``scout_walk_calls``, the retry loop's rounds, and
    ``scout_scan_steps``, the scan steps, each summed over shards.
    """
    B = int(np.asarray(n_chunks).shape[0])
    capacity = int(np.asarray(txns.arrival).shape[0])
    ncs = np.asarray(n_chunks, np.int32)
    shard_steps = sum(
        int(ncs[s * per_shard:(s + 1) * per_shard].max(initial=0))
        * per_shard for s in range(max(1, n_shards))
    )
    outs, perf = _run_compiled(
        bscout_group_key(sig, capacity, per_shard, k_max, fixed, n_shards,
                         backend),
        (scal, np.asarray(seeds, np.uint32), txns, tt, ncs),
        (P("lanes"), P("lanes"), P(None, "lanes"), P(None, "lanes"),
         P("lanes")),
        lanes=B, capacity=capacity, n_shards=n_shards, has_scout=True,
        steps=shard_steps, t_pack=t_pack, aux=True,
    )
    dfs = np.asarray(perf.pop("aux"))
    perf["dfs_steps_padded"] = int(np.sum(dfs[:, 0], dtype=np.int64))
    starts = range(0, B, per_shard)
    perf["scout_walk_calls"] = int(sum(int(dfs[s, 3]) for s in starts))
    perf["scout_scan_steps"] = CHUNK * int(
        sum(int(ncs[s:s + per_shard].max(initial=0)) for s in starts))
    return outs, dfs[:, 1:3], perf


class SimResult(NamedTuple):
    design: str
    completion: np.ndarray  # ticks, per txn (valid only)
    latency: np.ndarray  # ticks, per txn
    req_latency: np.ndarray  # ticks, per host request (GC excluded)
    wait: np.ndarray
    conflict: np.ndarray
    hops: np.ndarray
    tries: np.ndarray
    misroutes: np.ndarray
    exec_ticks: int
    bus_hold_ticks: int
    link_hold_ticks: int
    flash_energy_j: float
    transfer_energy_j: float
    static_energy_j: float
    # --- host-request surface (aligned with req_latency, request order) ---
    req_completion: np.ndarray | None = None  # ticks, max over request's txns
    req_tenant: np.ndarray | None = None  # tenant id per request, or None
    # --- fault surface (ISSUE 8; None on results predating the model) ---
    failed: np.ndarray | None = None  # bool per txn — permanent path failure
    req_failed: np.ndarray | None = None  # bool per request (any txn failed)

    @property
    def exec_s(self) -> float:
        return self.exec_ticks * TICK_NS * 1e-9

    @property
    def energy_j(self) -> float:
        return self.flash_energy_j + self.transfer_energy_j + self.static_energy_j

    @property
    def avg_power_w(self) -> float:
        return self.energy_j / max(self.exec_s, 1e-12)

    def iops(self, n_requests: int | None = None) -> float:
        n = len(self.req_latency) if n_requests is None else n_requests
        return n / max(self.exec_s, 1e-12)

    def latency_percentiles_us(self, qs=(50, 95, 99)) -> dict:
        """Host-request latency percentiles, us (GC excluded)."""
        if len(self.req_latency) == 0:
            return {f"p{q:g}": 0.0 for q in qs}
        v = np.percentile(self.req_latency, qs) * (TICK_NS * 1e-3)
        return {f"p{q:g}": float(x) for q, x in zip(qs, v)}

    def p99_latency_us(self) -> float:
        return float(np.percentile(self.req_latency, 99)) * TICK_NS * 1e-3

    def latency_cdf_us(self):
        lat = np.sort(self.req_latency) * (TICK_NS * 1e-3)
        return lat, np.arange(1, len(lat) + 1) / len(lat)

    def tenant_latencies(self) -> dict:
        """Per-tenant host-request latency arrays (ticks).  The concatenation
        over tenants is a permutation of ``req_latency`` — per-tenant
        metrics merge back to the untagged aggregate bit-exactly."""
        if self.req_tenant is None:
            return {0: self.req_latency}
        return {int(t): self.req_latency[self.req_tenant == t]
                for t in np.unique(self.req_tenant)}

    def conflict_rate(self) -> float:
        return float(np.mean(self.conflict))

    def failure_rate(self) -> float:
        """Fraction of transactions that permanently failed (dead path)."""
        if self.failed is None or len(self.failed) == 0:
            return 0.0
        return float(np.mean(self.failed))

    def iops_ok(self, n_requests: int | None = None) -> float:
        """Throughput counting only requests with NO failed transaction —
        the degraded-mode retention metric (a timed-out request is not
        service)."""
        if self.req_failed is None:
            return self.iops(n_requests)
        n_all = len(self.req_latency) if n_requests is None else n_requests
        n_ok = n_all - int(np.sum(self.req_failed))
        return n_ok / max(self.exec_s, 1e-12)


def _pad_to(n: int) -> int:
    """Bucket pad lengths to limit recompiles.

    Powers of 4: compile cost per program dwarfs the cost of scanning the
    extra padded (cond-skipped) steps, so fewer/coarser buckets win."""
    size = 1024
    while size < n:
        size *= 4
    return size


def _nominal_order_ref(cfg: SSDConfig, txns) -> np.ndarray:
    """Reference (per-transaction loop) for :func:`_nominal_order` — kept as
    the parity oracle for the vectorized grouped-cumsum pass below."""
    arrival = np.asarray(txns["arrival"], dtype=np.int64)
    kind = np.asarray(txns["kind"])
    plane = np.asarray(txns["plane"])
    nbytes = np.asarray(txns["nbytes"], dtype=np.int64)
    arr_order = np.argsort(arrival, kind="stable")
    plane_avail = np.zeros((cfg.n_planes,), dtype=np.int64)
    xfer_est = nbytes // TICK_NS  # ~1 B/ns
    nominal = np.zeros_like(arrival)
    t_r, t_w, t_e = cfg.t_read, cfg.t_prog, cfg.t_erase
    for i in arr_order:
        p = plane[i]
        s = max(arrival[i], plane_avail[p])
        k = kind[i]
        if k == KIND_READ:
            ready = s + 1 + t_r
            nominal[i] = ready
            plane_avail[p] = ready + xfer_est[i]
        elif k == KIND_WRITE:
            nominal[i] = s
            plane_avail[p] = s + xfer_est[i] + t_w
        else:
            nominal[i] = s
            plane_avail[p] = s + t_e
    return np.argsort(nominal, kind="stable")


def _nominal_times(cfg: SSDConfig, txns, avail0: np.ndarray | None = None):
    """Nominal per-txn readiness times plus the post-stream per-plane FIFO
    availability — the carry the streaming engine threads across windows.

    Vectorized as a grouped-cumsum pass (bit-exact to
    :func:`_nominal_order_ref` when ``avail0`` is None/zero): per plane, the
    FIFO recurrence ``avail' = max(arrival, avail) + d`` unrolls to
    ``avail_k = max(avail0_p, max_{j<k}(arrival_j - D_j)) + D_k`` with ``D``
    the in-plane exclusive prefix sum of the durations ``d`` — a segmented
    cumsum plus a segmented running max over plane groups.  ``avail0``
    generalizes the 0 floor to a carried initial plane availability (>= 0).

    Returns ``(nominal int64 [n], avail_out int64 [n_planes])``.
    """
    arrival = np.asarray(txns["arrival"], dtype=np.int64)
    n = len(arrival)
    out_avail = (np.zeros((cfg.n_planes,), dtype=np.int64)
                 if avail0 is None else np.asarray(avail0, np.int64).copy())
    if n == 0:
        return np.empty((0,), dtype=np.int64), out_avail
    kind = np.asarray(txns["kind"])
    plane = np.asarray(txns["plane"])
    nbytes = np.asarray(txns["nbytes"], dtype=np.int64)
    xfer_est = nbytes // TICK_NS  # ~1 B/ns
    t_r, t_w, t_e = cfg.t_read, cfg.t_prog, cfg.t_erase
    d = np.where(
        kind == KIND_READ, 1 + t_r + xfer_est,
        np.where(kind == KIND_WRITE, xfer_est + t_w, np.int64(t_e)),
    ).astype(np.int64)
    # contiguous plane groups, (arrival, original index)-ordered within each
    o = np.lexsort((np.arange(n), arrival, plane))
    p_s, a_s, d_s = plane[o], arrival[o], d[o]
    start = np.empty(n, dtype=bool)
    start[0] = True
    start[1:] = p_s[1:] != p_s[:-1]
    excl = np.cumsum(d_s) - d_s
    # in-group exclusive prefix sum: subtract each group's start value
    # (``excl`` is nondecreasing, so a running max forward-fills the starts)
    D = excl - np.maximum.accumulate(np.where(start, excl, -1))
    v = a_s - D
    # segmented running max via the monotone-offset trick: group ranks are
    # nondecreasing along the sort, so adding rank*span makes accumulation
    # never cross a group boundary
    gid = np.cumsum(start) - 1
    span = np.int64(v.max()) - np.int64(v.min()) + 1
    m = np.maximum.accumulate(v + gid * span) - gid * span
    # exclusive shift within the group; floor = the initial plane_avail
    m_excl = np.empty(n, dtype=np.int64)
    m_excl[1:] = m[:-1]
    m_excl[start] = 0
    avail = np.maximum(m_excl, out_avail[p_s]) + D
    s = np.maximum(a_s, avail)
    nom_s = s + np.where(kind[o] == KIND_READ, np.int64(1 + t_r), 0)
    nominal = np.empty(n, dtype=np.int64)
    nominal[o] = nom_s
    # each plane group's last element carries the whole group's FIFO end
    ends = np.flatnonzero(np.concatenate((start[1:], [True])))
    out_avail[p_s[ends]] = np.maximum(a_s[ends], avail[ends]) + d_s[ends]
    return nominal, out_avail


def _nominal_order(cfg: SSDConfig, txns) -> np.ndarray:
    """Order transactions by *nominal network-transfer time* (FIFO per plane,
    zero network contention).  The scan commits resources in this order, so
    commitments are near-chronological — the property that makes the in-order
    O(1)-state commit faithful to an event-driven simulator.  A write stuck
    behind a 100 us tPROG no longer reserves links/buses ahead of thousands
    of transfers that really happen first.
    """
    nominal, _ = _nominal_times(cfg, txns)
    return np.argsort(nominal, kind="stable")


def _nominal_order_carry(cfg: SSDConfig, txns, avail0: np.ndarray):
    """Streaming variant: order the window's transactions with the carried
    per-plane FIFO availability as the floor; returns ``(order, avail_out)``
    with ``avail_out`` in the window's (rebased) tick frame."""
    nominal, avail_out = _nominal_times(cfg, txns, avail0)
    return np.argsort(nominal, kind="stable"), avail_out


_M64 = np.uint64(0xFFFFFFFFFFFFFFFF)


def _read_retry_extra(faults, kind: np.ndarray, node: np.ndarray,
                      arrival: np.ndarray, plane: np.ndarray) -> np.ndarray:
    """Deterministic read-retry latency-ladder extension (ticks, int32).

    Chip-level read-retry (DDR-NAND tail model): each read on an afflicted
    chip independently fails its sense with probability ``retry_prob`` per
    ladder rung, paying that rung's extra ticks, until a rung succeeds or
    the ladder is exhausted.  The draw is a splitmix64 hash of the
    transaction's (arrival, plane) and the FaultSpec's ``retry_seed`` —
    design-independent, so every lane of a sweep sees the identical
    extended reads and the sweep stays an apples-to-apples comparison.
    """
    sel = kind == KIND_READ
    if faults.retry_chips:  # () = every chip afflicted
        sel &= np.isin(node, np.asarray(faults.retry_chips))
    extra = np.zeros((len(kind),), np.int64)
    if not sel.any():
        return extra
    with np.errstate(over="ignore"):  # wraparound is the hash
        base = (arrival.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)
                + plane.astype(np.uint64) * np.uint64(0xBF58476D1CE4E5B9)
                + np.uint64(faults.retry_seed & 0xFFFFFFFF))
    alive = sel.copy()
    for i, rung in enumerate(faults.retry_ladder):
        inc = np.uint64(((i + 1) * 0x9E3779B97F4A7C15) & 0xFFFFFFFFFFFFFFFF)
        z = (base + inc) & _M64
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9) & _M64
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB) & _M64
        z = z ^ (z >> np.uint64(31))
        u = (z >> np.uint64(11)).astype(np.float64) * (2.0 ** -53)
        alive = alive & (u < faults.retry_prob)
        if not alive.any():
            break
        extra = np.where(alive, extra + int(rung), extra)
    return extra


def _pack_txns(cfg: SSDConfig, txns, order: np.ndarray, faults=None):
    """Reorder numpy transaction fields into (host) TxnArrays, unpadded.

    Capacity padding happens at group-stack time (the planner pads each
    lane to its pool's capacity bucket), so the packed arrays here are the
    natural length and can be re-sliced per channel row without copies of
    the padding.  ``faults`` (a ``designs.FaultSpec``) applies the
    read-retry latency ladder to ``op_ticks`` host-side — the scan steps
    stay RNG-free and every design lane shares the extension."""
    n = len(order)

    def f(name, dtype):
        return np.asarray(txns[name])[order].astype(dtype)

    kind = f("kind", np.int32)
    op = np.where(
        kind == KIND_READ,
        cfg.t_read,
        np.where(kind == KIND_WRITE, cfg.t_prog, cfg.t_erase),
    ).astype(np.int32)
    if faults is not None and faults.retry_active:
        op = (op + _read_retry_extra(
            faults, kind, f("node", np.int64), f("arrival", np.int64),
            f("plane", np.int64),
        )).astype(np.int32)

    arrs = TxnArrays(
        arrival=f("arrival", np.int32),
        kind=kind,
        plane=f("plane", np.int32),
        node=f("node", np.int32),
        row=f("row", np.int32),
        nbytes=f("nbytes", np.int32),
        op_ticks=op,
        valid=np.ones((n,), dtype=bool),
    )
    return arrs, op


def _finish_result(cfg: SSDConfig, design: str, txns, order,
                   op: np.ndarray, outs: StepOut, n: int) -> SimResult:
    """Numpy post-processing of one lane's scan outputs into a SimResult.

    ``outs`` holds this lane's per-transaction numpy arrays in scan
    (ordered) space, length >= n (the planner merges channel-decomposed
    rows back into that space before calling)."""
    completion = outs.completion[:n]
    arrival = np.asarray(txns["arrival"])[order]
    latency = completion - arrival
    exec_ticks = int(completion.max() - arrival.min()) if n else 0

    # host-request latency: completion of a request = max over its page txns
    req = np.asarray(txns["req"])[order]
    n_req = int(req.max()) + 1 if len(req) and req.max() >= 0 else 0
    req_done = np.zeros((n_req,), np.int64)
    req_arr = np.full((n_req,), np.iinfo(np.int64).max)
    host = req >= 0
    np.maximum.at(req_done, req[host], completion[host].astype(np.int64))
    np.minimum.at(req_arr, req[host], arrival[host].astype(np.int64))
    seen = req_arr < np.iinfo(np.int64).max
    req_latency = (req_done - req_arr)[seen]
    req_completion = req_done[seen]
    failed = (np.asarray(outs.failed[:n], bool)
              if getattr(outs, "failed", None) is not None
              else np.zeros((n,), bool))
    req_fail = np.zeros((n_req,), bool)
    np.logical_or.at(req_fail, req[host], failed[host])
    req_failed = req_fail[seen]
    tenant = getattr(txns, "tenant_of_req", None)
    req_tenant = None
    if tenant is not None and len(tenant) >= n_req:
        req_tenant = np.asarray(tenant, np.int32)[:n_req][seen]

    pm = cfg.power
    tick_s = TICK_NS * 1e-9
    kind = np.asarray(txns["kind"])[order].astype(np.int32)
    die_w = np.where(
        kind == KIND_READ,
        pm.die_read_w,
        np.where(kind == KIND_WRITE, pm.die_prog_w, pm.die_erase_w),
    )
    flash_energy = float(np.sum(op.astype(np.float64) * tick_s * die_w))
    bus_hold = int(outs.bus_hold[:n].astype(np.int64).sum())
    link_hold = int(outs.link_hold[:n].astype(np.int64).sum())
    transfer_energy = (
        bus_hold * tick_s * pm.bus_active_w + link_hold * tick_s * pm.link_active_w
    )
    n_routers = REGISTRY[design].n_routers(build_mesh(cfg.rows, cfg.cols))
    static_energy = (pm.static_w + n_routers * pm.router_w) * exec_ticks * tick_s

    return SimResult(
        design=design,
        completion=completion,
        latency=latency,
        req_latency=req_latency,
        wait=outs.wait[:n],
        conflict=outs.conflict[:n],
        hops=outs.hops[:n],
        tries=outs.tries[:n],
        misroutes=outs.misroutes[:n],
        exec_ticks=exec_ticks,
        bus_hold_ticks=bus_hold,
        link_hold_ticks=link_hold,
        flash_energy_j=flash_energy,
        transfer_energy_j=float(transfer_energy),
        static_energy_j=float(static_energy),
        req_completion=req_completion,
        req_tenant=req_tenant,
        failed=failed,
        req_failed=req_failed,
    )


def simulate_sweep(
    cfg: SSDConfig,
    txns,
    designs: Sequence[str] = DESIGNS,
    seeds: int | Sequence[int] = 0,
    decompose: bool | str = "auto",
    faults=None,
) -> list[SimResult]:
    """Run the whole design sweep as batched, sharded jitted programs.

    ``txns`` is a dict/namespace with numpy fields: arrival (ticks int),
    kind, plane, node, row, nbytes, req (see ``repro.ssd.ftl``).
    ``designs`` are registry names (a name may repeat, e.g. to sweep seeds
    for one design); ``seeds`` is one int for every lane or a per-lane
    sequence.  Returns SimResults in lane order.

    Execution is delegated to the sweep planner (``repro.ssd.sweep_plan``):
    lanes are grouped per cost class, statically-routed lanes whose lowered
    masks are provably row-confined are decomposed by channel row
    (``decompose``: True / False / "auto" — all three are bit-identical;
    the flag only gates the perf transformation), and lane groups are
    sharded across host CPU devices.  Results are bit-identical to the flat
    single-lane scan for every design.

    ``faults`` (a ``designs.FaultSpec`` or None) injects hardware faults —
    lowered into per-design availability masks — plus the read-retry
    ladder.  ``None`` and an empty FaultSpec run the identical (bit-exact)
    fault-free program; the executables and their cache keys are shared
    either way, since the fault data rides the tables as arguments.
    """
    from repro.ssd.sweep_plan import execute_sim_runs

    designs = tuple(designs)
    resolve_specs(designs)
    if isinstance(seeds, (int, np.integer)):
        seeds = (int(seeds),) * len(designs)
    seeds = tuple(int(s) for s in seeds)
    if len(seeds) != len(designs):
        raise ValueError(
            f"got {len(seeds)} seeds for {len(designs)} design lanes"
        )
    run = (cfg, txns, designs, seeds, decompose)
    if faults is not None:
        run = run + (faults,)
    return execute_sim_runs([run])[0]


def simulate(cfg: SSDConfig, txns, design: str, seed: int = 0,
             faults=None) -> SimResult:
    """Run one (config, design) simulation — a 1-lane design sweep.

    This is the flat-scan parity oracle for the decomposed/sharded paths:
    it never channel-decomposes.  Like every lane, it runs the shared
    design-agnostic executable of its (geometry, capacity, cost class,
    promotions) — only the 1-lane pool's *promotions* specialize it."""
    return simulate_sweep(cfg, txns, (design,), (seed,), decompose=False,
                          faults=faults)[0]
