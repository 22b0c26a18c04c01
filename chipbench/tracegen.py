"""The benchmark's own traffic generator: synthetic block traces calibrated
to the Table-2 statistics of the Venice paper (read %, mean request size,
mean inter-arrival time), with ON/OFF bursty arrivals, lognormal sizes and
a hot-extent / sequential-stream / uniform address mix.

A copy of the simulator's generator, kept here so that the traffic of every
cell stays fixed when the program's generator changes;
``test_tracegen.py`` pins the two bit-equal for every workload and trace
seed the cells use.  ``accelerate`` and ``to_pages`` are the replay steps
the reference applies before its FTL: arrivals scaled so the offered load
reaches ``target_util`` of the shared channels' bandwidth (never slowed
down), then byte offsets cut into pages.
"""
from __future__ import annotations

import zlib
from typing import Dict, NamedTuple

import numpy as np


class WorkloadStats(NamedTuple):
    read_pct: float  # % of requests that are reads
    avg_kb: float  # mean request size, KB
    avg_iat_us: float  # mean inter-request arrival time, us


# name -> WorkloadStats, verbatim from Table 2
WORKLOADS: Dict[str, WorkloadStats] = {
    "hm_0": WorkloadStats(36, 8.8, 58),
    "mds_0": WorkloadStats(12, 9.6, 268),
    "proj_3": WorkloadStats(95, 9.6, 19),
    "prxy_0": WorkloadStats(3, 7.2, 242),
    "rsrch_0": WorkloadStats(9, 9.6, 129),
    "src1_0": WorkloadStats(56, 43.2, 49),
    "src2_1": WorkloadStats(98, 59.2, 50),
    "usr_0": WorkloadStats(40, 22.8, 98),
    "wdev_0": WorkloadStats(20, 9.2, 162),
    "web_1": WorkloadStats(54, 29.6, 67),
    "YCSB_B": WorkloadStats(99, 65.7, 13),
    "YCSB_D": WorkloadStats(99, 62, 14),
    "jenkins": WorkloadStats(94, 33.4, 615),
    "postgres": WorkloadStats(82, 13.3, 382),
    "LUN0": WorkloadStats(76, 20.4, 218),
    "LUN2": WorkloadStats(73, 16, 320),
    "LUN3": WorkloadStats(7, 7.7, 3127),
    "ssd-00": WorkloadStats(91, 90, 5),
    "ssd-10": WorkloadStats(99, 11.5, 2),
}

_ALIGN = 4096  # requests are 4KB-aligned multiples


def _seq_stream_offsets(
    off: np.ndarray,
    sz_align: np.ndarray,
    is_seq: np.ndarray,
    stream_of: np.ndarray,
    n_align: int,
) -> np.ndarray:
    """Resolve sequential-stream addresses without a per-request loop.

    Semantics (the former scalar loop): every request advances its stream's
    cursor to ``offset + size``; a sequential request first *reads* the
    cursor (mod ``n_align``) as its offset, a random request resets the
    cursor to its own random offset.  Because ``(x % n + s) % n == (x + s)
    % n``, a run of sequential requests between two resets is a prefix sum:
    ``offset_k = (base + sum of sizes of earlier seq requests in the run)
    % n_align`` where ``base`` is the cursor left by the last reset (0 at
    stream start).  That turns the whole recurrence into one grouped
    cumulative sum over (stream, arrival-order).
    """
    n = len(off)
    if n == 0 or not is_seq.any():
        return off
    order = np.argsort(stream_of, kind="stable")  # stream-major, arrival order
    s_s = stream_of[order]
    seq_s = is_seq[order]
    off_s = off[order].copy()
    sz_s = sz_align[order]
    # exclusive prefix sum of seq sizes (within the stream-major layout)
    excl = np.concatenate(([0], np.cumsum(np.where(seq_s, sz_s, 0))))[:-1]
    idx = np.arange(n, dtype=np.int64)
    # latest reset (= non-seq request) at or before each position …
    reset_at = np.maximum.accumulate(np.where(~seq_s, idx, -1))
    # … clipped to the current stream: positions before the stream's first
    # request belong to another stream ⇒ base cursor 0
    starts = np.concatenate(([0], np.flatnonzero(s_s[1:] != s_s[:-1]) + 1))
    counts = np.diff(np.concatenate((starts, [n])))
    start_of = np.repeat(starts, counts)
    in_stream = reset_at >= start_of
    r = np.clip(reset_at, 0, None)
    base = np.where(in_stream, off_s[r] + sz_s[r], 0)
    run_sum = excl - np.where(in_stream, excl[r], excl[start_of])
    off_s[seq_s] = (base + run_sum)[seq_s] % n_align
    out = off.copy()
    out[order] = off_s
    return out


def gen_trace(
    name: str,
    n_requests: int,
    seed: int = 0,
    footprint_bytes: int = 128 << 20,
    hot_weight: float = 0.6,
    n_extents: int = 4,
    extent_kb: int = 256,
    burst_mean: float = 64.0,
    burst_speed: float = 64.0,
    seq_frac: float = 0.5,
    n_streams: int = 8,
    stats: WorkloadStats | None = None,
) -> Dict[str, np.ndarray]:
    """Generate one synthetic trace in *byte* units (page-size agnostic).

    Arrivals use an ON/OFF burst process (deep-queue submission, like the
    originals): bursts of ~``burst_mean`` requests arrive ``burst_speed``×
    faster than the mean rate, separated by long gaps; the *overall mean*
    inter-arrival time equals Table 2's value exactly in expectation.

    ``stats`` overrides the Table-2 lookup by ``name``.
    """
    read_pct, avg_kb, avg_iat_us = (
        stats if stats is not None else WORKLOADS[name]
    )
    rs = np.random.RandomState((zlib.crc32(name.encode()) & 0x7FFFFFFF) ^ seed)

    # arrivals: ON/OFF bursts with exact mean IAT
    m, s = burst_mean, burst_speed
    in_burst = rs.rand(n_requests) < (m - 1.0) / m
    iat_b = avg_iat_us / s
    iat_g = avg_iat_us * (m - (m - 1.0) / s)  # preserves the Table-2 mean
    iat = np.where(
        in_burst,
        rs.exponential(iat_b, n_requests),
        rs.exponential(iat_g, n_requests),
    )
    iat *= avg_iat_us / iat.mean()  # exact-mean correction (like sizes)
    arrival = np.cumsum(iat)

    # sizes: lognormal with target mean, 4KB-aligned, heavy tail
    sigma = 0.7
    mu = np.log(avg_kb * 1024) - sigma * sigma / 2
    size = rs.lognormal(mu, sigma, n_requests)
    size = np.maximum(_ALIGN, (size / _ALIGN).round() * _ALIGN)
    # exact-mean correction (keeps Table 2 average request size)
    size *= (avg_kb * 1024) / size.mean()
    size = np.maximum(_ALIGN, (size / _ALIGN).round() * _ALIGN).astype(np.int64)

    is_read = rs.rand(n_requests) < (read_pct / 100.0)

    # addresses: three-way mixture, calibrated to enterprise-trace structure:
    #   * hot refs target a handful of small contiguous *extents* (hot files,
    #     indexes, metadata — typically 100s of KB).  A small extent occupies many
    #     chips of few channels under die-first superpage layout, which is
    #     exactly the access pattern that serializes a shared-bus SSD while a
    #     path-diverse interconnect reaches all of the extent's chips at once;
    #   * sequential streams (scans / file reads) walk contiguous ranges;
    #   * the rest is uniform over the footprint.
    n_align = footprint_bytes // _ALIGN
    hot = rs.rand(n_requests) < hot_weight
    ext_pages = max(1, (extent_kb * 1024) // _ALIGN)
    ext_base = rs.randint(0, max(1, n_align - ext_pages), n_extents)
    # zipf-ish popularity over extents
    pop = 1.0 / np.arange(1, n_extents + 1)
    pop /= pop.sum()
    ext_of = rs.choice(n_extents, n_requests, p=pop)
    off_hot = ext_base[ext_of] + rs.randint(0, ext_pages, n_requests)
    off = np.where(hot, off_hot, rs.randint(0, n_align, n_requests)).astype(np.int64)
    sz_align = (size // _ALIGN).astype(np.int64)
    is_seq = (rs.rand(n_requests) < seq_frac) & ~hot
    stream_of = rs.randint(0, n_streams, n_requests)
    off = _seq_stream_offsets(off, sz_align, is_seq, stream_of, n_align)

    return {
        "name": name,
        "arrival_us": arrival,
        "is_read": is_read,
        "offset_bytes": off * _ALIGN,
        "size_bytes": size,
        "footprint_bytes": footprint_bytes,
    }


def offered_utilization(trace: dict, chan_gbps: float, rows: int) -> float:
    """Offered load as a share of the channels' aggregate bandwidth."""
    span_us = float(trace["arrival_us"][-1] - trace["arrival_us"][0])
    tot_bytes = float(np.sum(trace["size_bytes"]))
    return tot_bytes / max(span_us, 1e-9) / (chan_gbps * 1e3 * rows)


def accelerate(trace: dict, chan_gbps: float, rows: int,
               target_util: float) -> dict:
    u = offered_utilization(trace, chan_gbps, rows)
    factor = max(1.0, target_util / max(u, 1e-9))
    if factor > 1.0:
        trace = dict(trace, arrival_us=trace["arrival_us"] / factor)
    return trace


def to_pages(trace: dict, page_bytes: int) -> dict:
    off = trace["offset_bytes"] // page_bytes
    last = ((trace["offset_bytes"] + trace["size_bytes"] + page_bytes - 1)
            // page_bytes)
    return {
        "arrival_us": trace["arrival_us"],
        "is_read": trace["is_read"],
        "offset_page": off.astype(np.int64),
        "n_pages": np.maximum(1, last - off).astype(np.int64),
        "footprint_pages": max(1, trace["footprint_bytes"] // page_bytes),
    }
