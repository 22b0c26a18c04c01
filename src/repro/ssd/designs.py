"""Declarative design registry: every interconnect design lowers to tables.

This module is the table-driven substrate behind ``repro.ssd.sim``.  A
:class:`DesignSpec` describes one interconnect design (shared-bus groups,
link tables, routing mode, bandwidth multipliers, scout parameters) and
:func:`lower_designs` lowers any set of specs into one *common padded array
layout* (:class:`LaneTables`) consumed by the simulator's single jitted scan
step.  Because every design is data — not code — the whole design space runs
as one batched (vmapped) program sharing one compiled executable, and adding
a design is a ~20-line spec here instead of simulator surgery.

Unified resource space
  Every time-shared resource lives in one padded vector of length ``R_pad``:

      [ 0, L_pad )                 links   (mesh links / shared buses)
      [ L_pad, L_pad+F_pad )       flash controllers
      [ L_pad+F_pad, R_pad )       chip I/O interfaces

  A design's route is a boolean *combined mask* over this vector: a shared
  bus is a 1-link "mesh" with routing disabled (its mask holds exactly one
  link bit), pnSSD's two bus paths are two candidate masks, NoSSD's XY path
  is a multi-link mask, and Venice's mask is produced at runtime by the
  Algorithm-1 scout.  Degenerate designs disable routing by scouting a
  zero-length path (``dst == src``).

Timing tables
  Transfer time is one rational formula per design,
  ``ns = ceil(nbytes * xfer_num / xfer_den) + hops * hop_ns`` (then ticks =
  ceil(ns / TICK_NS)), which reproduces both the shared-channel rate
  (xfer_num/xfer_den = 1000 / round(GB/s * 1000), hop_ns = 0) and the mesh
  Eq. (1) link rate (1 B/ns, +1 ns pipeline fill per hop).

Ablations (each documented next to its spec in ``REGISTRY``):
  venice_minimal  Algorithm 1 restricted to minimal-adaptive routing — no
                  misroutes; isolates the value of non-minimal adaptivity.
  venice_hold     the circuit is reserved across CMD + tR + transfer instead
                  of per transfer phase — quantifies wasted link-hours.
  venice_kscout   beyond-paper: 3 scouts race per reservation and the
                  fewest-hop success is committed — shorter circuits hold
                  fewer link-hours (paper fn. 3 hints at resend policies).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Sequence

import jax.numpy as jnp
import numpy as np

from repro.core.topology import MeshTopology, all_xy_paths, build_mesh
from repro.ssd.config import SSDConfig, TICK_NS

_BIG = np.int32(2**30)

KIND_BUS = "bus"
KIND_PNSSD = "pnssd"
KIND_NOSSD = "nossd"
KIND_SCOUT = "scout"
_KINDS = (KIND_BUS, KIND_PNSSD, KIND_NOSSD, KIND_SCOUT)


@dataclasses.dataclass(frozen=True)
class DesignSpec:
    """One interconnect design, declaratively.

    ``kind`` selects the lowering recipe (how the tables are built); all
    runtime behaviour differences between designs of the same kind are pure
    data in :class:`LaneTables`.
    """

    name: str
    kind: str  # one of _KINDS
    doc: str = ""
    # --- bus designs ---
    chan: str = "row"  # "row": one bus per channel; "node": private per chip
    bw_mult: float = 1.0  # channel bandwidth multiplier (pSSD: 2x)
    bus_ovh: bool = False  # pays cfg.t_bus_ovh per bus phase (legacy ONFI)
    # --- scout (Venice) designs ---
    allow_nonminimal: bool = True  # Algorithm-1 misrouting enabled
    hold_during_op: bool = False  # keep one circuit across CMD+tR+transfer
    n_scouts: int = 1  # scouts raced per reservation (k-scout ablation)
    d_est_hops: int = 0  # hop margin in the availability-estimate duration
    d_est_pad: int = 0  # constant tick margin in the estimate

    def __post_init__(self) -> None:
        if self.kind not in _KINDS:
            raise ValueError(f"unknown design kind {self.kind!r}")
        if self.n_scouts < 1:
            raise ValueError("n_scouts must be >= 1")

    @property
    def uses_mesh(self) -> bool:
        """Mesh-routed designs carry per-node routers (energy accounting)."""
        return self.kind in (KIND_NOSSD, KIND_SCOUT)

    @property
    def fc_nearest(self) -> bool:
        """Nearest-available FC selection (§4.2) vs fixed FC-per-channel."""
        return self.kind in (KIND_NOSSD, KIND_SCOUT)

    @property
    def counts_bus_energy(self) -> bool:
        """Occupancy billed as shared-bus hold (vs per-link hold)."""
        return self.kind in (KIND_BUS, KIND_PNSSD)

    def n_routers(self, topo: MeshTopology) -> int:
        return topo.n_nodes if self.uses_mesh else 0


REGISTRY: dict[str, DesignSpec] = {
    s.name: s
    for s in (
        DesignSpec(
            name="baseline", kind=KIND_BUS, chan="row", bus_ovh=True,
            doc="Multi-channel shared ONFI bus (Table 1): one bus per "
                "channel, per-phase protocol overhead.",
        ),
        DesignSpec(
            name="pssd", kind=KIND_BUS, chan="row", bw_mult=2.0,
            doc="Kim+ [15] pSSD: packetized channel (no ONFI overhead) at "
                "2x bandwidth.",
        ),
        DesignSpec(
            name="pnssd", kind=KIND_PNSSD,
            doc="Kim+ [15] pnSSD: row+column shared buses — two candidate "
                "paths per chip, FC i drives row bus i and column bus i.",
        ),
        DesignSpec(
            name="nossd", kind=KIND_NOSSD, d_est_hops=6,
            doc="Tavakkol+ [38] NoSSD: packet-switched 2D mesh, "
                "deterministic XY routing, nearest-available FC.",
        ),
        DesignSpec(
            name="venice", kind=KIND_SCOUT, d_est_hops=48, d_est_pad=16,
            doc="The paper (§4): per-transfer path reservation via "
                "Algorithm-1 scouts, non-minimal fully-adaptive.",
        ),
        DesignSpec(
            name="venice_minimal", kind=KIND_SCOUT, allow_nonminimal=False,
            d_est_hops=48, d_est_pad=16,
            doc="Ablation: Venice with minimal-only adaptive routing (no "
                "misroutes) — isolates non-minimal adaptivity's value.",
        ),
        DesignSpec(
            name="venice_hold", kind=KIND_SCOUT, hold_during_op=True,
            d_est_hops=48, d_est_pad=16,
            doc="Ablation: one circuit held across CMD + flash op + "
                "transfer — quantifies the link-hours the paper's "
                "per-transfer reservation recovers.",
        ),
        DesignSpec(
            name="venice_kscout", kind=KIND_SCOUT, n_scouts=3,
            d_est_hops=48, d_est_pad=16,
            doc="Beyond-paper k-scout: race 3 scouts with independent "
                "tie-break streams, commit the fewest-hop success.",
        ),
        DesignSpec(
            name="ideal", kind=KIND_BUS, chan="node", bus_ovh=True,
            doc="Path-conflict-free ideal: a private channel per chip "
                "(same ONFI protocol as baseline, just never shared).",
        ),
    )
}

DESIGNS = tuple(REGISTRY)


@dataclasses.dataclass(frozen=True)
class FaultSpec:
    """Hardware faults injected into a lowered design (ISSUE 8).

    All faults are named in the *mesh* frame (link ids from
    :func:`repro.core.topology.build_mesh`, router = mesh node, FC = channel
    row) and each design's lowering maps them onto its own resource
    structure — a dead horizontal link in row ``r`` kills the whole shared
    bus ``r`` for bus designs but only one hop for mesh designs, which is
    the degraded-mode asymmetry the fault model exists to measure.

    Read-retry (``retry_*``) models the chip-level latency tail of marginal
    NAND reads: each read on an afflicted chip independently retries with
    probability ``retry_prob`` per ladder rung, adding the rung's ticks.
    It is applied host-side to transaction op times (deterministic per
    ``retry_seed``) so every design sees the identical extended reads.

    An all-default (empty) FaultSpec lowers to all-False masks and is
    bit-identical to the fault-free path by construction.
    """

    failed_links: tuple = ()    # mesh link ids
    failed_routers: tuple = ()  # mesh node ids — every port of the node dies
    failed_fcs: tuple = ()      # flash-controller ids (channel rows)
    retry_chips: tuple = ()     # chip/node ids with read-retry; () = none
    retry_prob: float = 0.0     # per-rung retry probability for reads
    retry_ladder: tuple = ()    # extra ticks per successive retry rung
    retry_seed: int = 0         # deterministic retry draw stream

    def __post_init__(self) -> None:
        for f in ("failed_links", "failed_routers", "failed_fcs",
                  "retry_chips"):
            object.__setattr__(
                self, f, tuple(sorted({int(x) for x in getattr(self, f)}))
            )
        object.__setattr__(
            self, "retry_ladder", tuple(int(x) for x in self.retry_ladder)
        )
        if not (0.0 <= self.retry_prob <= 1.0):
            raise ValueError(f"retry_prob must be in [0,1], got {self.retry_prob}")
        if any(t < 0 for t in self.retry_ladder):
            raise ValueError("retry_ladder ticks must be >= 0")

    @property
    def hw_faulty(self) -> bool:
        return bool(self.failed_links or self.failed_routers or self.failed_fcs)

    @property
    def retry_active(self) -> bool:
        return self.retry_prob > 0.0 and bool(self.retry_ladder)

    def __bool__(self) -> bool:
        return self.hw_faulty or self.retry_active

    def dead_sets(self, topo: MeshTopology) -> tuple[set, set]:
        """(dead mesh link ids, dead FC ids) — routers expand to their ports."""
        for l in self.failed_links:
            if not 0 <= l < topo.n_links:
                raise ValueError(f"failed link {l} out of range [0,{topo.n_links})")
        for n in self.failed_routers:
            if not 0 <= n < topo.n_nodes:
                raise ValueError(f"failed router {n} out of range [0,{topo.n_nodes})")
        for f in self.failed_fcs:
            if not 0 <= f < topo.rows:
                raise ValueError(f"failed FC {f} out of range [0,{topo.rows})")
        dead_links = set(self.failed_links)
        for n in self.failed_routers:
            dead_links.update(
                int(l) for l in topo.port_link[n] if l >= 0
            )
        return dead_links, set(self.failed_fcs)


NO_FAULTS = FaultSpec()


def static_design_names(names: Sequence[str] = DESIGNS) -> tuple:
    """The statically-routed designs among ``names`` — every design whose
    lane the batched runner (and its Pallas lane kernel) can serve; the
    complement is the scout-routed set, which needs the DFS scan."""
    return tuple(n for n in names if REGISTRY[n].kind != KIND_SCOUT)


class SweepLayout(NamedTuple):
    """Static padded sizes of the unified resource space for one config."""

    rows: int
    cols: int
    n_nodes: int
    n_links: int  # mesh links of the underlying topology
    L_pad: int  # link section width (covers every design's link count)
    F_pad: int  # flash-controller section width
    R_pad: int  # total combined resource vector width


def sweep_layout_geom(rows: int, cols: int) -> SweepLayout:
    topo = build_mesh(rows, cols)
    L_pad = max(topo.n_links, topo.n_nodes, rows + cols, 1)
    F_pad = max(rows, cols)
    return SweepLayout(
        rows=rows,
        cols=cols,
        n_nodes=topo.n_nodes,
        n_links=topo.n_links,
        L_pad=L_pad,
        F_pad=F_pad,
        R_pad=L_pad + F_pad + topo.n_nodes,
    )


def sweep_layout(cfg: SSDConfig) -> SweepLayout:
    return sweep_layout_geom(cfg.rows, cfg.cols)


class LaneTables(NamedTuple):
    """Per-design tables, stacked on a leading design axis.

    The simulator vmaps its scan over this axis: one compiled executable
    serves every lane.  All shapes depend only on the config, never on the
    design set, so different sweeps over the same config share the compile.
    """

    # --- scalars [D] ---
    is_scout: jnp.ndarray  # bool — route via Algorithm-1 scout
    fc_nearest: jnp.ndarray  # bool — nearest-available FC selection (§4.2)
    ovh: jnp.ndarray  # int32 — per-bus-phase protocol overhead (ticks)
    cmd_base_ns: jnp.ndarray  # int32 — command packet ns before hop term
    xfer_num: jnp.ndarray  # int32 — transfer ns = ceil(B*num/den) + hops*hop_ns
    xfer_den: jnp.ndarray  # int32
    hop_ns: jnp.ndarray  # int32 — per-hop ns (0 for buses)
    allow_nonmin: jnp.ndarray  # bool — scout may misroute
    hold: jnp.ndarray  # bool — venice_hold circuit policy
    n_scouts: jnp.ndarray  # int32 — scouts raced per reservation
    d_est_hops: jnp.ndarray  # int32 — availability-estimate hop margin
    d_est_pad: jnp.ndarray  # int32 — availability-estimate tick margin
    count_bus: jnp.ndarray  # bool — bill occupancy as bus-hold
    # --- tables ---
    cmask: jnp.ndarray  # bool [D, F_pad, n_nodes, 2, R_pad] combined masks
    hops: jnp.ndarray  # int32 [D, F_pad, n_nodes, 2]
    cand2_ok: jnp.ndarray  # bool [D, n_nodes] — second candidate path valid
    fc_fixed: jnp.ndarray  # int32 [D, n_nodes, 2] — fixed FC per candidate
    dist: jnp.ndarray  # int32 [D, F_pad, n_nodes] — FC->chip distance
    fc_valid: jnp.ndarray  # bool [D, F_pad]
    fc_node: jnp.ndarray  # int32 [D, F_pad] — mesh injection node per FC
    res_dead: jnp.ndarray  # bool [D, R_pad] — failed-resource mask (ISSUE 8)


def _fault_mask(topo: MeshTopology, lay: SweepLayout, spec: DesignSpec,
                faults: FaultSpec | None) -> tuple[np.ndarray, set]:
    """Lower mesh-frame faults onto one design's resource vector.

    Returns ``(res_dead [R_pad] bool, dead_fcs)``.  Shared-bus designs
    inherit a fault anywhere on the structure the bus replaces: a dead
    horizontal link in row ``r`` (or FC ``r``) kills bus ``r`` outright,
    which is exactly the "one fault strands the channel" cliff Venice's
    path diversity avoids.  Vertical links / routers have no bus analogue
    (chan="row" buses have neither) and are ignored there.
    """
    res_dead = np.zeros((lay.R_pad,), dtype=bool)
    if faults is None or not faults.hw_faulty:
        return res_dead, set()
    dead_links, dead_fcs = faults.dead_sets(topo)
    rows, cols = lay.rows, lay.cols
    n_h = rows * (cols - 1)  # horizontal link ids precede vertical (topology)
    if spec.kind == KIND_BUS and spec.chan == "row":
        for l in dead_links:
            if l < n_h:  # horizontal link in row r => shared bus r dead
                res_dead[l // max(cols - 1, 1)] = True
        for f in dead_fcs:
            res_dead[f] = True  # FC f drives bus f
    elif spec.kind == KIND_BUS:  # chan == "node": private channel per chip
        for l in dead_links:
            for n in topo.link_endpoints[l]:
                res_dead[int(n)] = True
        for f in dead_fcs:  # FC f serves row f's private channels
            res_dead[f * cols:(f + 1) * cols] = True
    elif spec.kind == KIND_PNSSD:
        for l in dead_links:
            if l < n_h:
                res_dead[l // max(cols - 1, 1)] = True  # row bus
            else:
                res_dead[rows + (l - n_h) // max(rows - 1, 1)] = True  # col bus
        for f in dead_fcs:
            res_dead[lay.L_pad + f] = True
    else:  # mesh kinds (nossd / scout): faults map 1:1
        for l in dead_links:
            res_dead[l] = True
        for f in dead_fcs:
            res_dead[lay.L_pad + f] = True
    return res_dead, dead_fcs


def _lower_one(cfg: SSDConfig, topo: MeshTopology, lay: SweepLayout,
               spec: DesignSpec, faults: FaultSpec | None = None) -> dict:
    """Lower one spec to numpy tables in the unified padded layout."""
    rows, cols, N = lay.rows, lay.cols, lay.n_nodes
    L0, F0, R = lay.L_pad, lay.F_pad, lay.R_pad
    node_row = np.arange(N) // cols
    node_col = np.arange(N) % cols

    cmask = np.zeros((F0, N, 2, R), dtype=bool)
    hops = np.zeros((F0, N, 2), dtype=np.int32)
    cand2_ok = np.zeros((N,), dtype=bool)
    fc_fixed = np.zeros((N, 2), dtype=np.int32)
    dist = np.full((F0, N), _BIG, dtype=np.int32)
    fc_valid = np.zeros((F0,), dtype=bool)
    fc_valid[:rows] = True
    fc_node = np.zeros((F0,), dtype=np.int32)
    fc_node[:rows] = topo.fc_node

    # mesh manhattan distance from each FC's injection node (f, 0)
    mesh_dist = (
        np.abs(np.arange(rows)[:, None] - node_row[None, :]) + node_col[None, :]
    ).astype(np.int32)

    if spec.kind == KIND_BUS:
        link = node_row if spec.chan == "row" else np.arange(N)
        for n in range(N):
            cmask[:, n, :, link[n]] = True
        fc_fixed[:, 0] = fc_fixed[:, 1] = node_row
        dist[:rows] = 0
    elif spec.kind == KIND_PNSSD:
        # candidate 0: the chip's row bus, driven by FC row; candidate 1:
        # its column bus (ids rows..rows+cols-1), driven by FC col.  Both
        # candidates additionally occupy the chip's single I/O interface and
        # the owning FC (pnSSD adds path diversity, not transfer engines).
        for n in range(N):
            r, c = node_row[n], node_col[n]
            for cand, (lnk, fc) in enumerate(((r, r), (rows + c, c))):
                cmask[:, n, cand, lnk] = True
                cmask[:, n, cand, L0 + fc] = True
                cmask[:, n, cand, L0 + F0 + n] = True
            fc_fixed[n] = (r, c)
        cand2_ok[:] = True
        dist[:rows] = 0
    elif spec.kind == KIND_NOSSD:
        paths_np, hops_np = all_xy_paths(topo)
        for f in range(rows):
            for n in range(N):
                lk = paths_np[f, n]
                cmask[f, n, :, lk[lk >= 0]] = True
                cmask[f, n, :, L0 + f] = True
                cmask[f, n, :, L0 + F0 + n] = True
                hops[f, n] = hops_np[f, n]
        dist[:rows] = hops_np  # XY hops == manhattan distance
    else:  # KIND_SCOUT — route masks come from the scout at runtime
        dist[:rows] = mesh_dist

    res_dead, dead_fcs = _fault_mask(topo, lay, spec, faults)
    if spec.fc_nearest:
        # nearest-available FC selection must never pick a dead controller
        for f in dead_fcs:
            fc_valid[f] = False

    if spec.kind in (KIND_BUS, KIND_PNSSD):
        mult = spec.bw_mult
        xfer_num, xfer_den = 1000, int(round(cfg.chan_gbps * mult * 1000))
        hop_ns = 0
        cmd_base_ns = cfg.t_cmd * TICK_NS  # lowers back to exactly t_cmd ticks
        ovh = cfg.t_bus_ovh if spec.bus_ovh else 0
    else:
        xfer_num, xfer_den = 1, 1  # Eq. (1): 8-bit links at 1 GHz = 1 B/ns
        hop_ns = 1
        cmd_base_ns = 8  # 8-byte command packet
        ovh = 0

    return dict(
        is_scout=spec.kind == KIND_SCOUT,
        fc_nearest=spec.fc_nearest,
        ovh=np.int32(ovh),
        cmd_base_ns=np.int32(cmd_base_ns),
        xfer_num=np.int32(xfer_num),
        xfer_den=np.int32(xfer_den),
        hop_ns=np.int32(hop_ns),
        allow_nonmin=spec.allow_nonminimal,
        hold=spec.hold_during_op,
        n_scouts=np.int32(spec.n_scouts),
        d_est_hops=np.int32(spec.d_est_hops),
        d_est_pad=np.int32(spec.d_est_pad),
        count_bus=spec.counts_bus_energy,
        cmask=cmask,
        hops=hops,
        cand2_ok=cand2_ok,
        fc_fixed=fc_fixed,
        dist=dist,
        fc_valid=fc_valid,
        fc_node=fc_node,
        res_dead=res_dead,
    )


@functools.lru_cache(maxsize=None)
def lower_designs(cfg: SSDConfig, names: tuple,
                  faults: FaultSpec | None = None) -> LaneTables:
    """Lower ``names`` (design names, in order) into stacked LaneTables.

    ``faults`` (hashable, part of the memo key) lowers hardware faults into
    per-design ``res_dead`` availability masks; ``None`` (and any empty
    FaultSpec) produces all-False masks — the fault-free tables are
    bit-identical to the pre-fault-model lowering.
    """
    for d in names:
        if d not in REGISTRY:
            raise ValueError(f"unknown design {d!r}; one of {DESIGNS}")
    topo = build_mesh(cfg.rows, cfg.cols)
    lay = sweep_layout(cfg)
    lowered = [_lower_one(cfg, topo, lay, REGISTRY[d], faults) for d in names]
    stacked = {
        k: jnp.asarray(np.stack([low[k] for low in lowered]))
        for k in lowered[0]
    }
    return LaneTables(**stacked)


def resolve_specs(designs: Sequence[str]) -> tuple:
    """Validate design names and return their specs (same order)."""
    try:
        return tuple(REGISTRY[d] for d in designs)
    except KeyError as e:
        raise ValueError(f"unknown design {e.args[0]!r}; one of {DESIGNS}")


# ---------------------------------------------------------------------------
# node-indexed tables of the batched small-lane runner
#
# The node-indexed tables (cmask/hops/dist/cand2_ok/fc_fixed) are static
# data, so the batched static step never looks them up itself: the planner
# ships each lane's tables node-major (``node_tables``, N rows), and the
# batched run gathers one chunk's rows per transaction on the device,
# outside the step (``sim._gather_node_rows``), from the chunk's
# transaction nodes.  Only state-dependent lookups (plane free-at, live FC
# selection) remain in the step, as one-hot compare-and-reduce
# (``repro.kernels.onehot``).  Candidate masks are bit-packed along the
# resource axis into little-endian 32-bit words (bit r is bit r % 32 of
# word r // 32) to keep the [N, F_pad, 2, R] tables at R/8 bytes; the
# step unpacks them with shifts (no gather either).
# ---------------------------------------------------------------------------


def mask_words_per_row(n_bits: int) -> int:
    """int32 words that hold one packed mask of ``n_bits`` resources."""
    return -(-n_bits // 32)


def node_tables(tables_row) -> dict:
    """One lane's node-indexed tables, node-major (numpy, N rows).

    ``tables_row``: one design's view of :class:`LaneTables` (no lane
    axis).  Returns ``mask_words`` int32 [N, F_pad, 2, ceil(R_pad/32)]
    (``cmask`` bit-packed), ``hops`` int32 [N, F_pad, 2], ``dist`` int32
    [N, F_pad], ``cand2`` bool [N], ``fc_fixed`` int32 [N, 2].
    """
    cmask = np.asarray(tables_row.cmask)  # [F0, N, 2, R]
    packed = np.packbits(cmask, axis=-1, bitorder="little")
    n_bytes = 4 * mask_words_per_row(cmask.shape[-1])
    packed = np.pad(packed, [(0, 0)] * 3 + [(0, n_bytes - packed.shape[-1])])
    words = np.ascontiguousarray(packed).view("<i4").astype(np.int32)
    return dict(
        mask_words=np.ascontiguousarray(words.transpose(1, 0, 2, 3)),
        hops=np.ascontiguousarray(
            np.asarray(tables_row.hops).transpose(1, 0, 2)),
        dist=np.ascontiguousarray(np.asarray(tables_row.dist).T),
        cand2=np.asarray(tables_row.cand2_ok),
        fc_fixed=np.asarray(tables_row.fc_fixed),
    )


def pregather_node_tables(tables_row, nodes: np.ndarray) -> dict:
    """One lane's node tables resolved per transaction on the host: the
    rows of :func:`node_tables` at ``nodes`` (int [n]), each of length n.
    The reference the device-side gather is tested against."""
    return {k: np.ascontiguousarray(v[nodes])
            for k, v in node_tables(tables_row).items()}


def pregather_scout_tables(tables_row, nodes: np.ndarray) -> dict:
    """Resolve one SCOUT lane's node-indexed tables per transaction.

    The scout step's only node-indexed design table is ``dist`` (FC
    selection + the command-packet hop estimate); the path itself is found
    at runtime by the DFS, so there are no candidate masks to pre-gather.
    Returns ``dist`` int32 [n, F_pad] (same layout contract as
    :func:`pregather_node_tables`).
    """
    return dict(
        dist=np.ascontiguousarray(np.asarray(tables_row.dist).T[nodes]),
    )


# ---------------------------------------------------------------------------
# channel-decomposition proof obligation
#
# The simulator may partition a lane's transactions by channel row and scan
# the rows as parallel lanes (cutting sequential scan length from N to
# ~N/rows) ONLY if the lane provably never couples state across rows.  That
# is a property of the lowered tables, so it is verified here, at lowering
# time, not assumed per design name: a lane qualifies iff its FC choice is
# static (nearest-available selection reads every FC's live state) and every
# resource its candidate masks can touch is touched by nodes of one row only.
# baseline/pssd/ideal pass (their bus is private to a row or a chip); pnssd
# fails (a column bus is shared by every row), nossd fails (dynamic FC +
# XY paths cross rows), and scout lanes fail by construction (the scout
# walks the global mesh).  Callers fall back to the flat scan on False.
# ---------------------------------------------------------------------------


def _mask_row_confined(lay: SweepLayout, low: dict) -> bool:
    """Proof check for one lowered lane (see block comment above)."""
    if bool(low["is_scout"]) or bool(low["fc_nearest"]):
        return False
    cmask = np.asarray(low["cmask"])
    fc_fixed = np.asarray(low["fc_fixed"])
    cand2_ok = np.asarray(low["cand2_ok"])
    owner = np.full((lay.R_pad,), -1, dtype=np.int64)
    for n in range(lay.n_nodes):
        r = n // lay.cols
        for cand in (0, 1):
            # an invalid second candidate is evaluated but value-dead
            # (``useA`` is forced), so only reachable masks are checked
            if cand == 1 and not cand2_ok[n]:
                continue
            used = np.flatnonzero(cmask[fc_fixed[n, cand], n, cand])
            clash = (owner[used] != -1) & (owner[used] != r)
            if clash.any():
                return False
            owner[used] = r
    return True


@functools.lru_cache(maxsize=None)
def rows_confined(cfg: SSDConfig, names: tuple) -> tuple:
    """Per-lane bool: may this lane's scan be decomposed by channel row?"""
    topo = build_mesh(cfg.rows, cfg.cols)
    lay = sweep_layout(cfg)
    return tuple(
        _mask_row_confined(lay, _lower_one(cfg, topo, lay, REGISTRY[d]))
        for d in names
    )
