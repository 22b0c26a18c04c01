"""Page-mapping FTL, one page at a time: host requests in, page
transactions out.

Out-of-place writes, greedy foreground GC with in-plane copyback (read +
write + erase, no network transfer, charged to no request), wear-aware
block choice, and chunked channel-way-die-plane striping.  The whole
footprint is written once, in LPN order, before the trace (a read always
finds a mapped page).
"""
from __future__ import annotations

import numpy as np

from chipbench.reference.ssdconfig import SSD

KIND_READ, KIND_WRITE, KIND_ERASE = 0, 1, 2


def stripe_plane(ssd: SSD, idx: int) -> int:
    idx //= max(1, ssd.chunk_pages)
    way = idx % ssd.cols
    idx //= ssd.cols
    ch = idx % ssd.rows
    idx //= ssd.rows
    die = idx % ssd.dies_per_chip
    idx //= ssd.dies_per_chip
    pl = idx % ssd.planes_per_die
    chip = ch * ssd.cols + way
    return (chip * ssd.dies_per_chip + die) * ssd.planes_per_die + pl


class FTL:
    GC_THRESHOLD = 2

    def __init__(self, ssd: SSD, n_lpns: int, overprovision: float = 1.28):
        self.ssd = ssd
        self.n_planes = ssd.n_planes
        self.ppb = ssd.pages_per_block
        phys = int(n_lpns * overprovision)
        bpp = -(-phys // (self.n_planes * self.ppb))
        self.bpp = max(bpp, self.GC_THRESHOLD + 2)
        self.ppp = self.bpp * self.ppb
        self.l2p = np.full((n_lpns,), -1, dtype=np.int64)
        self.p2l = np.full((self.n_planes * self.ppp,), -1, dtype=np.int64)
        shape = (self.n_planes, self.bpp)
        self.valid = np.zeros(shape, dtype=np.int64)
        self.written = np.zeros(shape, dtype=np.int64)
        self.erases = np.zeros(shape, dtype=np.int64)
        self.is_free = np.ones(shape, dtype=bool)
        self.is_free[:, 0] = False  # block 0 of every plane starts open
        self.open_block = np.zeros((self.n_planes,), dtype=np.int64)
        self.next_page = np.zeros((self.n_planes,), dtype=np.int64)
        self.stripe = 0

    def _victims(self, plane):
        full = (self.written[plane] >= self.ppb) & ~self.is_free[plane]
        full[self.open_block[plane]] = False
        return full

    def _alloc(self, plane, out, t, during_gc=False):
        if self.next_page[plane] >= self.ppb:
            self._open_block(plane, out, t, during_gc)
        block = self.open_block[plane]
        off = self.next_page[plane]
        self.next_page[plane] += 1
        self.written[plane, block] += 1
        return int(plane * self.ppp + block * self.ppb + off)

    def _open_block(self, plane, out, t, during_gc):
        if not during_gc:
            # one victim per triggering allocation, then defend the two
            # blocks of headroom that copyback draws from
            if (np.count_nonzero(self.is_free[plane]) <= self.GC_THRESHOLD
                    and self._victims(plane).any()):
                self._collect(plane, out, t)
            while np.count_nonzero(self.is_free[plane]) < 2:
                self._collect(plane, out, t)
            if self.next_page[plane] < self.ppb:
                return  # copyback reopened a block with room: keep filling
        free_ids = np.flatnonzero(self.is_free[plane])
        nxt = free_ids[np.argmin(self.erases[plane, free_ids])]
        self.is_free[plane, nxt] = False
        self.open_block[plane] = nxt
        self.next_page[plane] = 0

    def _collect(self, plane, out, t):
        cand = np.flatnonzero(self._victims(plane))
        victim = cand[np.argmin(self.valid[plane, cand])]
        base = plane * self.ppp + victim * self.ppb
        for off in range(self.ppb):
            lpn = self.p2l[base + off]
            if lpn < 0:
                continue
            new = self._alloc(plane, out, t, during_gc=True)
            self.l2p[lpn] = new
            self.p2l[new] = lpn
            self.p2l[base + off] = -1
            self.valid[plane, victim] -= 1
            self.valid[plane, new // self.ppb % self.bpp] += 1
            if out is not None:
                out.append((t, KIND_READ, plane, 0, -1))
                out.append((t, KIND_WRITE, plane, 0, -1))
        self.valid[plane, victim] = 0
        self.written[plane, victim] = 0
        self.is_free[plane, victim] = True
        self.erases[plane, victim] += 1
        if out is not None:
            out.append((t, KIND_ERASE, plane, 0, -1))

    def write(self, lpn, out, t):
        old = self.l2p[lpn]
        if old >= 0:
            pl = old // self.ppp
            self.valid[pl, (old % self.ppp) // self.ppb] -= 1
            self.p2l[old] = -1
        plane = stripe_plane(self.ssd, self.stripe)
        self.stripe += 1
        ppn = self._alloc(plane, out, t)
        self.l2p[lpn] = ppn
        self.p2l[ppn] = lpn
        self.valid[plane, (ppn % self.ppp) // self.ppb] += 1

    def plane_of(self, lpn) -> int:
        return int(self.l2p[lpn] // self.ppp)


def decompose(ssd: SSD, pages: dict) -> dict:
    """Page trace (``arrival_us``, ``is_read``, ``offset_page``,
    ``n_pages``, ``footprint_pages``) -> transactions sorted stably by
    arrival tick: ``arrival kind plane node row nbytes req`` (req -1 for
    GC work)."""
    fp = int(pages["footprint_pages"])
    ftl = FTL(ssd, fp)
    for lpn in range(fp):
        ftl.write(lpn, None, 0)
    rows = []
    for i in range(len(pages["arrival_us"])):
        t = ssd.us_to_ticks(float(pages["arrival_us"][i]))
        base = int(pages["offset_page"][i])
        for k in range(int(pages["n_pages"][i])):
            lpn = (base + k) % fp
            if pages["is_read"][i]:
                rows.append((t, KIND_READ, ftl.plane_of(lpn), ssd.page_bytes,
                             i))
            else:
                gc: list = []
                ftl.write(lpn, gc, t)
                rows.append((t, KIND_WRITE, ftl.plane_of(lpn),
                             ssd.page_bytes, i))
                rows.extend((tg, kd, pl, nb, -1) for tg, kd, pl, nb, _ in gc)
    arr = np.asarray(rows, dtype=np.int64).reshape(-1, 5)
    arr = arr[np.argsort(arr[:, 0], kind="stable")]
    chip = arr[:, 2] // (ssd.dies_per_chip * ssd.planes_per_die)
    return dict(arrival=arr[:, 0], kind=arr[:, 1], plane=arr[:, 2],
                node=chip, row=chip // ssd.cols, nbytes=arr[:, 3],
                req=arr[:, 4])
