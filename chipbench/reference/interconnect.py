"""The interconnect designs, in plain numpy: the flash-node mesh, Venice's
Algorithm-1 scout walk, and each design's routes lowered onto one padded
resource vector.

Resource vector of one lane (``R_pad`` entries):

    [0, L_pad)              links (mesh links, or shared buses)
    [L_pad, L_pad + F_pad)  flash controllers
    [L_pad + F_pad, R_pad)  chip I/O interfaces

A statically routed design gives each (controller, chip, candidate) a set
of resource ids its transfer occupies; Venice finds its path at run time.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from chipbench.reference.ssdconfig import SSD

BIG = 2**30
RIGHT, UP, LEFT, DOWN = 0, 1, 2, 3
N_PORTS = 4
OPPOSITE = (LEFT, DOWN, RIGHT, UP)

KIND_BUS, KIND_PNSSD, KIND_NOSSD, KIND_SCOUT = "bus", "pnssd", "nossd", "scout"


# ---- the mesh -------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Mesh:
    """``rows x cols`` flash nodes; controller ``f`` injects at (f, 0).
    Link ids: horizontal links row-major, then vertical links col-major."""

    rows: int
    cols: int
    n_nodes: int
    n_links: int
    port_link: np.ndarray  # [n_nodes, 4], -1 off the mesh
    port_neighbor: np.ndarray  # [n_nodes, 4], -1 off the mesh
    fc_node: np.ndarray  # [rows]


def build_mesh(rows: int, cols: int) -> Mesh:
    n_nodes = rows * cols
    n_h = rows * (cols - 1)
    port_link = np.full((n_nodes, N_PORTS), -1, dtype=np.int64)
    port_neighbor = np.full((n_nodes, N_PORTS), -1, dtype=np.int64)
    for r in range(rows):
        for c in range(cols):
            n = r * cols + c
            if c + 1 < cols:
                port_link[n, RIGHT] = r * (cols - 1) + c
                port_neighbor[n, RIGHT] = n + 1
            if c >= 1:
                port_link[n, LEFT] = r * (cols - 1) + c - 1
                port_neighbor[n, LEFT] = n - 1
            if r + 1 < rows:
                port_link[n, UP] = n_h + c * (rows - 1) + r
                port_neighbor[n, UP] = n + cols
            if r >= 1:
                port_link[n, DOWN] = n_h + c * (rows - 1) + r - 1
                port_neighbor[n, DOWN] = n - cols
    return Mesh(rows, cols, n_nodes, n_h + cols * (rows - 1), port_link,
                port_neighbor, np.arange(rows) * cols)


def xy_path(mesh: Mesh, src: int, dst: int) -> list:
    """Dimension-order route (columns first, then rows): NoSSD's path."""
    r, c = divmod(src, mesh.cols)
    r1, c1 = divmod(dst, mesh.cols)
    links = []
    while c != c1:
        port = RIGHT if c1 > c else LEFT
        links.append(int(mesh.port_link[r * mesh.cols + c, port]))
        c += 1 if c1 > c else -1
    while r != r1:
        port = UP if r1 > r else DOWN
        links.append(int(mesh.port_link[r * mesh.cols + c, port]))
        r += 1 if r1 > r else -1
    return links


# ---- Venice Algorithm 1 ---------------------------------------------------

def xorshift32(x: int) -> int:
    x &= 0xFFFFFFFF
    x ^= (x << 13) & 0xFFFFFFFF
    x ^= x >> 17
    x ^= (x << 5) & 0xFFFFFFFF
    return x


@dataclasses.dataclass
class ScoutWalk:
    success: bool
    path_links: list
    hops: int
    steps: int  # DFS steps, backtracks included
    misroutes: int


def _minimal_ports(mesh: Mesh, node: int, dst: int) -> list:
    r, c = divmod(node, mesh.cols)
    rd, cd = divmod(dst, mesh.cols)
    ports = []
    if cd > c:
        ports.append(RIGHT)
    elif cd < c:
        ports.append(LEFT)
    if rd > r:
        ports.append(UP)
    elif rd < r:
        ports.append(DOWN)
    return ports


def scout_walk(mesh: Mesh, src: int, dst: int, link_busy: np.ndarray,
               rng: int, allow_nonminimal: bool) -> ScoutWalk:
    """One scout, depth first: a free minimal port (random tie-break),
    else a free non-minimal port other than the one it came in by, else
    back up one hop and release that link.  Each output port of each
    router is tried at most once, so the walk ends."""
    busy = link_busy.copy()
    tried = np.zeros((mesh.n_nodes, N_PORTS), dtype=bool)
    stack = []  # (node, entry port, exit port, was a misroute)
    cur, entry, steps = src, -1, 0
    while True:
        steps += 1
        if cur == dst:
            links = [int(mesh.port_link[n, p]) for n, _, p, _ in stack]
            return ScoutWalk(True, links, len(links), steps,
                             sum(int(m) for *_, m in stack))

        def free(p):
            lnk = mesh.port_link[cur, p]
            return lnk >= 0 and not busy[lnk] and not tried[cur, p]

        cands = [p for p in _minimal_ports(mesh, cur, dst) if free(p)]
        misroute = False
        if not cands and allow_nonminimal:
            cands = [p for p in range(N_PORTS) if p != entry and free(p)]
            misroute = True
        if cands:
            if len(cands) > 1:
                rng = xorshift32(rng)
                pick = cands[rng % len(cands)]
            else:
                pick = cands[0]
            tried[cur, pick] = True
            busy[mesh.port_link[cur, pick]] = True
            stack.append((cur, entry, pick, misroute))
            entry = OPPOSITE[pick]
            cur = int(mesh.port_neighbor[cur, pick])
        else:
            if not stack:
                return ScoutWalk(False, [], 0, steps, 0)
            pnode, pentry, pexit, _ = stack.pop()
            busy[mesh.port_link[pnode, pexit]] = False
            cur, entry = pnode, pentry


# ---- the design registry --------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Design:
    name: str
    kind: str
    chan: str = "row"  # bus designs: "row" = one bus per channel, "node" = private
    bw_mult: float = 1.0
    bus_ovh: bool = False  # pays the ONFI protocol overhead per bus phase
    allow_nonminimal: bool = True
    hold_during_op: bool = False
    n_scouts: int = 1
    d_est_hops: int = 0
    d_est_pad: int = 0

    @property
    def fc_nearest(self) -> bool:
        return self.kind in (KIND_NOSSD, KIND_SCOUT)

    @property
    def counts_bus(self) -> bool:
        return self.kind in (KIND_BUS, KIND_PNSSD)


DESIGNS = {d.name: d for d in (
    Design("baseline", KIND_BUS, chan="row", bus_ovh=True),
    Design("pssd", KIND_BUS, chan="row", bw_mult=2.0),
    Design("pnssd", KIND_PNSSD),
    Design("nossd", KIND_NOSSD, d_est_hops=6),
    Design("venice", KIND_SCOUT, d_est_hops=48, d_est_pad=16),
    Design("venice_minimal", KIND_SCOUT, allow_nonminimal=False,
           d_est_hops=48, d_est_pad=16),
    Design("venice_hold", KIND_SCOUT, hold_during_op=True, d_est_hops=48,
           d_est_pad=16),
    Design("venice_kscout", KIND_SCOUT, n_scouts=3, d_est_hops=48,
           d_est_pad=16),
    Design("ideal", KIND_BUS, chan="node", bus_ovh=True),
)}


@dataclasses.dataclass
class Layout:
    rows: int
    cols: int
    n_nodes: int
    n_links: int
    L_pad: int
    F_pad: int
    R_pad: int


def layout(rows: int, cols: int) -> Layout:
    mesh = build_mesh(rows, cols)
    L_pad = max(mesh.n_links, mesh.n_nodes, rows + cols, 1)
    F_pad = max(rows, cols)
    return Layout(rows, cols, mesh.n_nodes, mesh.n_links, L_pad, F_pad,
                  L_pad + F_pad + mesh.n_nodes)


@dataclasses.dataclass
class Lowered:
    """One design's routes and timing constants."""

    design: Design
    paths: dict  # (fc, node, cand) -> list of resource ids
    hops: np.ndarray  # [F_pad, n_nodes, 2]
    cand2_ok: np.ndarray  # [n_nodes]
    fc_fixed: np.ndarray  # [n_nodes, 2]
    dist: np.ndarray  # [F_pad, n_nodes]
    fc_valid: np.ndarray  # [F_pad]
    fc_node: np.ndarray  # [F_pad]
    ovh: int
    cmd_base_ns: int
    xfer_num: int
    xfer_den: int
    hop_ns: int


def lower(ssd: SSD, name: str) -> Lowered:
    spec = DESIGNS[name]
    mesh = build_mesh(ssd.rows, ssd.cols)
    lay = layout(ssd.rows, ssd.cols)
    rows, cols, N = lay.rows, lay.cols, lay.n_nodes
    L0, F0 = lay.L_pad, lay.F_pad
    node_row = np.arange(N) // cols
    node_col = np.arange(N) % cols
    paths = {}
    hops = np.zeros((F0, N, 2), dtype=np.int64)
    cand2_ok = np.zeros((N,), dtype=bool)
    fc_fixed = np.zeros((N, 2), dtype=np.int64)
    dist = np.full((F0, N), BIG, dtype=np.int64)
    fc_valid = np.zeros((F0,), dtype=bool)
    fc_valid[:rows] = True
    fc_node = np.zeros((F0,), dtype=np.int64)
    fc_node[:rows] = mesh.fc_node
    if spec.kind == KIND_BUS:
        for n in range(N):
            bus = int(node_row[n]) if spec.chan == "row" else n
            for f in range(F0):
                for cand in (0, 1):
                    paths[f, n, cand] = [bus]
        fc_fixed[:, 0] = fc_fixed[:, 1] = node_row
        dist[:rows] = 0
    elif spec.kind == KIND_PNSSD:
        # candidate 0: the chip's row bus from controller r; candidate 1:
        # its column bus from controller c; both also hold that controller
        # and the chip's interface
        for n in range(N):
            r, c = int(node_row[n]), int(node_col[n])
            for cand, (bus, fc) in enumerate(((r, r), (rows + c, c))):
                for f in range(F0):
                    paths[f, n, cand] = sorted([bus, L0 + fc, L0 + F0 + n])
            fc_fixed[n] = (r, c)
        cand2_ok[:] = True
        dist[:rows] = 0
    elif spec.kind == KIND_NOSSD:
        for f in range(F0):
            for n in range(N):
                if f < rows:
                    links = xy_path(mesh, int(mesh.fc_node[f]), n)
                    ids = sorted(set(links) | {L0 + f, L0 + F0 + n})
                    hops[f, n] = len(links)
                    dist[f, n] = len(links)
                else:
                    ids = []
                for cand in (0, 1):
                    paths[f, n, cand] = ids
    else:
        dist[:rows] = (np.abs(np.arange(rows)[:, None] - node_row[None, :])
                       + node_col[None, :])
    if spec.kind in (KIND_BUS, KIND_PNSSD):
        xfer_num = 1000
        xfer_den = int(round(ssd.chan_gbps * spec.bw_mult * 1000))
        hop_ns = 0
        cmd_base_ns = ssd.t_cmd * ssd.tick_ns
        ovh = ssd.t_bus_ovh if spec.bus_ovh else 0
    else:
        xfer_num, xfer_den, hop_ns, cmd_base_ns, ovh = 1, 1, 1, 8, 0
    return Lowered(spec, paths, hops, cand2_ok, fc_fixed, dist, fc_valid,
                   fc_node, ovh, cmd_base_ns, xfer_num, xfer_den, hop_ns)
