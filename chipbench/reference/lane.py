"""One design lane of the SSD, one transaction at a time.

Transactions are committed in nominal order: each plane serves its
transactions first come first served with no network contention, and the
lane walks them by the time each would reach the network.  Every time-shared
resource (link, bus, flash controller, chip interface) keeps the time it
is next free plus one remembered idle gap that a later, shorter transfer
may fill.

A statically routed transaction tries its one or two candidate paths and
keeps the one that finishes first.  A Venice transaction picks the nearest
available controller and sends scouts (``interconnect.scout_walk``) until
one reserves a path, retrying at the next time any link frees.
"""
from __future__ import annotations

import numpy as np

from chipbench.reference.ftl import KIND_READ, KIND_WRITE
from chipbench.reference.interconnect import (
    BIG, KIND_SCOUT, build_mesh, layout, lower, scout_walk,
)
from chipbench.reference.ssdconfig import SSD

FAIL_TIMEOUT = 1 << 20
MAX_TRIES = 64
OUT_FIELDS = ("completion", "wait", "conflict", "hops", "tries",
              "scout_steps", "misroutes", "bus_hold", "link_hold", "failed")


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def nominal_order(ssd: SSD, txns: dict) -> np.ndarray:
    """Order by the time each transaction would reach the network if every
    plane served its own queue first come first served."""
    arrival = txns["arrival"]
    plane_avail = np.zeros((ssd.n_planes,), dtype=np.int64)
    nominal = np.zeros((len(arrival),), dtype=np.int64)
    for i in np.argsort(arrival, kind="stable"):
        p = txns["plane"][i]
        s = max(int(arrival[i]), int(plane_avail[p]))
        xfer = int(txns["nbytes"][i]) // ssd.tick_ns  # ~1 B/ns
        k = txns["kind"][i]
        if k == KIND_READ:
            nominal[i] = s + 1 + ssd.t_read
            plane_avail[p] = nominal[i] + xfer
        elif k == KIND_WRITE:
            nominal[i] = s
            plane_avail[p] = s + xfer + ssd.t_prog
        else:
            nominal[i] = s
            plane_avail[p] = s + ssd.t_erase
    return np.argsort(nominal, kind="stable")


# ---- one resource: free-at plus one remembered gap --------------------------

def _gap_avail(gs, ge, fa, e, d):
    s = max(e, gs)
    return s if s + d <= ge else max(e, fa)


def _gap_commit(gs, ge, fa, s, e2):
    if s >= gs and e2 <= ge:  # inside the remembered gap
        return (gs, s, fa) if (s - gs) >= (ge - e2) else (e2, ge, fa)
    new_idle = max(s, fa) - fa
    if (ge - gs) >= new_idle:
        return gs, ge, max(fa, e2)
    return fa, max(s, fa), max(fa, e2)


class Resources:
    """``free``, ``gs``, ``ge`` arrays over a resource vector."""

    def __init__(self, n: int):
        self.free = [0] * n
        self.gs = [0] * n
        self.ge = [0] * n

    def copy(self) -> "Resources":
        r = Resources(0)
        r.free, r.gs, r.ge = list(self.free), list(self.gs), list(self.ge)
        return r

    def avail(self, i, e, d):
        return _gap_avail(self.gs[i], self.ge[i], self.free[i], e, d)

    def commit(self, i, s, e2):
        self.gs[i], self.ge[i], self.free[i] = _gap_commit(
            self.gs[i], self.ge[i], self.free[i], s, e2)

    def busy_at(self, i, t, d):
        return not (t >= self.free[i]
                    or (t >= self.gs[i] and t + d <= self.ge[i]))

    def sched_gap(self, i, e, d, enable):
        s = self.avail(i, e, d) if enable else e
        if enable:
            self.commit(i, s, s + d)
        return s

    def path_sched(self, ids, e, d):
        """Earliest common start on every resource of a path."""
        s1 = max([0, e] + [self.avail(i, e, d) for i in ids])
        if not any(self.busy_at(i, s1, d) for i in ids):
            return s1
        return max([e, 0] + [self.free[i] for i in ids])


def _fc_select(avail, dist_row, tcand):
    """Closest controller free now, else the earliest free (first minimum
    wins ties)."""
    free_now = [a <= tcand for a in avail]
    if any(free_now):
        fc = int(np.argmin([d if f else BIG
                            for d, f in zip(dist_row, free_now)]))
    else:
        fc = int(np.argmin(avail))
    return fc, max(tcand, avail[fc])


class Lane:
    def __init__(self, ssd: SSD, design: str):
        self.ssd = ssd
        self.low = lower(ssd, design)
        self.spec = self.low.design
        self.scout = self.spec.kind == KIND_SCOUT
        self.mesh = build_mesh(ssd.rows, ssd.cols)
        self.lay = layout(ssd.rows, ssd.cols)

    def _cmd_ticks(self, hops):
        ns = self.low.cmd_base_ns + hops * self.low.hop_ns
        return max(_ceil_div(ns, self.ssd.tick_ns), 1)

    def _xfer_ticks(self, nbytes, hops):
        ns = _ceil_div(nbytes * self.low.xfer_num, self.low.xfer_den)
        return _ceil_div(ns + hops * self.low.hop_ns, self.ssd.tick_ns)

    def _d_est(self, nbytes, is_read, op):
        d = (self._xfer_ticks(nbytes, self.spec.d_est_hops)
             + self.spec.d_est_pad)
        return d + op if (self.spec.hold_during_op and is_read) else d

    # -- statically routed --------------------------------------------------
    def _static(self, st, tx):
        plane_free, res = st["plane"], st["res"]
        low, L0 = self.low, self.lay.L_pad
        is_read = tx["kind"] == KIND_READ
        tcand = max(tx["arrival"], plane_free[tx["plane"]])
        d_est = self._d_est(tx["nbytes"], is_read, tx["op"])
        if self.spec.fc_nearest:
            avail = [res.avail(L0 + f, tcand, d_est) if low.fc_valid[f]
                     else BIG for f in range(self.lay.F_pad)]
            fc, t0 = _fc_select(avail, [int(low.dist[f, tx["node"]])
                                        for f in range(self.lay.F_pad)],
                                tcand)
            fcA = fcB = fc
        else:
            t0 = tcand
            fcA, fcB = (int(x) for x in low.fc_fixed[tx["node"]])
        cand2 = bool(low.cand2_ok[tx["node"]])

        def attempt(fc, cand, enable):
            ids = low.paths[fc, tx["node"], cand]
            hops = int(low.hops[fc, tx["node"], cand])
            cmd = self._cmd_ticks(hops)
            xfer = self._xfer_ticks(tx["nbytes"], hops)
            d0 = low.ovh + cmd + (0 if is_read else xfer)
            r = res.copy()
            s0 = r.path_sched(ids, t0, d0)
            if enable:
                for i in ids:
                    r.commit(i, s0, s0 + d0)
            op_end = s0 + d0 + tx["op"]
            d1 = low.ovh + xfer
            s1 = r.path_sched(ids, op_end, d1)
            if enable and is_read:
                for i in ids:
                    r.commit(i, s1, s1 + d1)
            done = s1 + d1 if is_read else op_end
            wait = (s0 - t0) + (s1 - op_end if is_read else 0)
            occ = d0 + (d1 if is_read else 0)
            return r, done, wait, occ, hops

        rA, doneA, waitA, occA, hopsA = attempt(fcA, 0, True)
        rB, doneB, waitB, occB, hopsB = attempt(fcB, 1, cand2)
        useA = doneA <= (doneB if cand2 else BIG)
        r, done, wait, occ, hops = ((rA, doneA, waitA, occA, hopsA) if useA
                                    else (rB, doneB, waitB, occB, hopsB))
        st["res"] = r
        plane_free[tx["plane"]] = done
        return dict(completion=done, wait=wait, conflict=wait > 0, hops=hops,
                    tries=1, scout_steps=0, misroutes=0,
                    bus_hold=occ if self.spec.counts_bus else 0,
                    link_hold=0 if self.spec.counts_bus else hops * occ,
                    failed=False)

    # -- Venice ---------------------------------------------------------------
    def _scout_until_success(self, links, src, dst, t0, rng, d_hold):
        nl = self.mesh.n_links
        free = np.asarray(links.free, dtype=np.int64)
        gs = np.asarray(links.gs, dtype=np.int64)
        ge = np.asarray(links.ge, dtype=np.int64)

        def try_once(t, rng):
            busy = ~((t >= free[:nl])
                     | ((t >= gs[:nl]) & (t + d_hold <= ge[:nl])))
            best = None
            for _ in range(self.spec.n_scouts):
                rng = ((rng * 747796405 + 2891336453) & 0xFFFFFFFF) | 1
                w = scout_walk(self.mesh, src, dst, busy, rng,
                               self.spec.allow_nonminimal)
                if best is None or (w.success and (not best.success
                                                   or w.hops < best.hops)):
                    best = w
            return best, rng

        walk, rng = try_once(t0, rng)
        t, tries = t0, 1
        while not walk.success and tries < MAX_TRIES:
            later = np.concatenate((free[free > t], gs[gs > t]))
            t_next = max(int(later.min()) if len(later) else BIG, t + 1)
            if tries + 1 >= MAX_TRIES:
                t_next = int(free.max())
            walk, rng = try_once(t_next, rng)
            t = t_next
            tries += 1
        return walk, t, rng, tries

    def _venice(self, st, tx):
        plane_free, links, fcs, chips = (st["plane"], st["links"],
                                         st["fcs"], st["chips"])
        low = self.low
        n_fcs = self.lay.rows
        is_read = tx["kind"] == KIND_READ
        hold = self.spec.hold_during_op
        tcand = max(tx["arrival"], plane_free[tx["plane"]])
        d_est = self._d_est(tx["nbytes"], is_read, tx["op"])
        avail = [fcs.avail(f, tcand, d_est) if low.fc_valid[f] else BIG
                 for f in range(n_fcs)]
        fc, t0 = _fc_select(avail, [int(low.dist[f, tx["node"]])
                                    for f in range(n_fcs)], tcand)
        src = int(low.fc_node[fc])
        cmd_pkt = self._cmd_ticks(int(low.dist[fc, tx["node"]]))
        s_cmd = fcs.sched_gap(fc, t0, cmd_pkt, is_read and not hold)
        ready_r = s_cmd + cmd_pkt + tx["op"]
        t_nonread = max(t0, chips.avail(tx["node"], t0, d_est))
        t_read = max(ready_r, fcs.avail(fc, ready_r, d_est),
                     chips.avail(tx["node"], ready_r, d_est))
        t_xfer_req = t_read if is_read else t_nonread
        walk, t_resv, st["rng"], tries = self._scout_until_success(
            links, src, tx["node"], t0 if hold else t_xfer_req, st["rng"],
            d_est)
        hops = walk.hops
        start = t_resv + _ceil_div((walk.steps + hops) * self.ssd.scout_hop_ns,
                                   self.ssd.tick_ns)
        cmd_v = self._cmd_ticks(hops)
        xfer_v = self._xfer_ticks(tx["nbytes"], hops)
        end_p = start + (xfer_v if is_read else cmd_v + xfer_v)
        if hold:
            done_r = start + cmd_v + tx["op"] + xfer_v
            data_end_w = start + cmd_v + xfer_v
            commit_end = done_r if is_read else data_end_w
            done = done_r if is_read else data_end_w + tx["op"]
            wait = start - t0
        else:
            commit_end = end_p
            done = end_p if is_read else end_p + tx["op"]
            wait = (s_cmd - t0) + (start - t_xfer_req)
        if not walk.success:
            done, wait = tcand + FAIL_TIMEOUT, FAIL_TIMEOUT
        else:
            for lnk in walk.path_links:
                links.commit(lnk, t_resv, commit_end)
            fcs.commit(fc, t_resv, commit_end)
            chips.commit(tx["node"], t_resv, commit_end)
        plane_free[tx["plane"]] = done
        return dict(completion=done, wait=wait,
                    conflict=(tries > 1) or not walk.success, hops=hops,
                    tries=tries, scout_steps=walk.steps,
                    misroutes=walk.misroutes, bus_hold=0,
                    link_hold=(0 if not walk.success
                               else hops * (commit_end - t_resv)),
                    failed=not walk.success)

    def run(self, packed: dict, seed: int) -> dict:
        n_planes = self.ssd.n_planes
        if self.scout:
            st = dict(plane=[0] * n_planes, links=Resources(self.lay.L_pad),
                      fcs=Resources(self.lay.rows),
                      chips=Resources(self.lay.n_nodes), rng=seed | 1)
            step = self._venice
        else:
            st = dict(plane=[0] * n_planes, res=Resources(self.lay.R_pad))
            step = self._static
        outs = {k: [] for k in OUT_FIELDS}
        cols = [packed[k].tolist() for k in
                ("arrival", "kind", "plane", "node", "nbytes", "op")]
        for a, k, p, nd, nb, op in zip(*cols):
            o = step(st, dict(arrival=a, kind=k, plane=p, node=nd, nbytes=nb,
                              op=op))
            for f in OUT_FIELDS:
                outs[f].append(o[f])
        return {k: np.asarray(v, dtype=bool if k in ("conflict", "failed")
                              else np.int64) for k, v in outs.items()}


def simulate(ssd: SSD, txns: dict, design: str, lane_seed: int) -> dict:
    """Scan order outputs of one design over decomposed transactions, plus
    ``req_latency`` (per host request, in request order, GC excluded) and
    ``exec_ticks``."""
    order = nominal_order(ssd, txns)
    kind = txns["kind"][order]
    op = np.where(kind == KIND_READ, ssd.t_read,
                  np.where(kind == KIND_WRITE, ssd.t_prog, ssd.t_erase))
    packed = dict(arrival=txns["arrival"][order], kind=kind,
                  plane=txns["plane"][order], node=txns["node"][order],
                  nbytes=txns["nbytes"][order], op=op)
    out = Lane(ssd, design).run(packed, lane_seed)
    arrival = packed["arrival"]
    comp = out["completion"]
    out["exec_ticks"] = int(comp.max() - arrival.min()) if len(comp) else 0
    req = txns["req"][order]
    host = req >= 0
    n_req = int(req.max()) + 1 if host.any() else 0
    done = np.zeros((n_req,), np.int64)
    first = np.full((n_req,), np.iinfo(np.int64).max)
    np.maximum.at(done, req[host], comp[host])
    np.minimum.at(first, req[host], arrival[host])
    seen = first < np.iinfo(np.int64).max
    out["req_latency"] = (done - first)[seen]
    return out
