"""Streamed, memory-bounded ingestion of real storage traces.

Two wire formats are understood:

* **MSR-Cambridge CSV** (the paper's primary suite): positional columns
  ``Timestamp,Hostname,DiskNumber,Type,Offset,Size,ResponseTime`` with the
  timestamp in Windows FILETIME units (100 ns ticks) and ``Type`` one of
  ``Read``/``Write``.
* **blktrace-style CSV**: ``time,op,offset,size`` where ``time`` is seconds
  (float), ``op`` contains ``R`` or ``W`` (blkparse RWBS convention — e.g.
  ``R``, ``WS``, ``RA``), ``offset`` is the start *sector* (512 B) and
  ``size`` the sector count.

Both parse to the canonical byte-trace dict the rest of the repo consumes
(``arrival_us`` f64 starting at 0, ``is_read`` bool, ``offset_bytes`` /
``size_bytes`` int64, ``footprint_bytes``) — the same schema
``repro.traces.generator.gen_trace`` emits, so an ingested trace drops into
``to_pages`` → FTL → sweep unchanged.

Parsing is **streamed**: :func:`iter_trace_csv` reads line-by-line and
yields fixed-size numpy batches, holding at most ``batch_requests`` rows in
Python lists at any time, so week-long multi-GB traces ingest in bounded
memory.  :func:`load_trace` is the whole-file convenience built on the same
row parser; the two paths are pinned identical on the bundled fixture by
``tests/test_workloads.py``.

Real traces address a whole LUN (offsets up to hundreds of GB) while the
simulator's FTL allocates physical pages for the entire footprint, so
:func:`compact_footprint` remaps the sparse touched address set onto a
dense range by merging touched extents: page-adjacency *within* an extent
(the sequentiality that matters to striping and channel skew) is preserved,
untouched gaps between extents are dropped.
"""
from __future__ import annotations

import gzip
import os
import warnings
from typing import Dict, Iterator

import numpy as np

from repro.ssd.config import TICK_NS
from repro.traces.generator import register_trace

__all__ = [
    "sniff_format", "iter_trace_csv", "load_trace", "compact_footprint",
    "write_msr_csv", "ingest_file", "arrival_ticks_i64",
    "iter_trace_windows",
]

_FILETIME_PER_US = 10.0  # Windows FILETIME = 100 ns ticks
_SECTOR = 512


def _open_text(path: str):
    """Text handle for a trace file; ``.gz`` paths stream through gzip
    transparently (real MSR distributions ship as ``.csv.gz``)."""
    if path.endswith(".gz"):
        return gzip.open(path, "rt")
    return open(path)


def _parse_rows_msr(rows: list, base: int | None) -> tuple:
    """Columns (ts_us, is_read, offset, size, base) from split MSR fields.

    FILETIME values (~1.3e17) exceed float64's exact-integer range, so the
    timestamp is rebased to the file's FIRST row in int64 arithmetic before
    the float conversion — a week-long trace spans ≪ 2^53 after rebasing.
    """
    ticks = np.array([int(r[0]) for r in rows], np.int64)
    if base is None:
        base = int(ticks[0])
    ts = (ticks - base) / _FILETIME_PER_US
    is_read = np.array([r[3].strip().lower().startswith("r") for r in rows],
                       bool)
    off = np.array([int(r[4]) for r in rows], np.int64)
    size = np.array([int(r[5]) for r in rows], np.int64)
    return ts, is_read, off, size, base


def _parse_rows_blk(rows: list, base: int | None) -> tuple:
    ts = np.array([float(r[0]) for r in rows], np.float64) * 1e6  # s -> us
    is_read = np.array(["r" in r[1].strip().lower() for r in rows], bool)
    off = np.array([int(r[2]) for r in rows], np.int64) * _SECTOR
    size = np.array([int(r[3]) for r in rows], np.int64) * _SECTOR
    return ts, is_read, off, size, base


_PARSERS = {"msr": _parse_rows_msr, "blktrace": _parse_rows_blk}

# cheap per-row validity probes (same conversions the batch parsers apply,
# scalar) — a row that passes its probe cannot fail the vectorized parse
_VALIDATORS = {
    "msr": lambda r: (int(r[0]), int(r[4]), int(r[5])),
    "blktrace": lambda r: (float(r[0]), int(r[2]), int(r[3])),
}


def _is_header(line: str) -> bool:
    first = line.split(",", 1)[0].strip()
    try:
        float(first)
        return False
    except ValueError:
        return True


def sniff_format(path: str) -> str:
    """``"msr"`` or ``"blktrace"`` from the first data line's shape."""
    with _open_text(path) as f:
        for line in f:
            line = line.strip()
            if not line or _is_header(line):
                continue
            fields = line.split(",")
            if len(fields) >= 6 and fields[3].strip().lower() in (
                    "read", "write"):
                return "msr"
            if len(fields) >= 4:
                return "blktrace"
            break
    raise ValueError(f"cannot sniff trace format of {path}")


def iter_trace_csv(
    path: str, fmt: str = "auto", batch_requests: int = 65536,
    on_error: str = "raise", stats: dict | None = None,
) -> Iterator[Dict[str, np.ndarray]]:
    """Stream a trace CSV as numpy batches of ≤ ``batch_requests`` rows.

    Each batch is a dict with raw (un-normalized) columns ``arrival_us``
    (rebased to the file's first data row), ``is_read``, ``offset_bytes``,
    ``size_bytes``.  Memory is bounded by the batch size — the file is
    never read whole.

    Corrupted rows (too few fields, or unparseable numeric columns) are
    governed by ``on_error``: ``"raise"`` (default) raises ``ValueError``
    naming the line, ``"skip"`` drops the row and counts it in
    ``stats["skipped_rows"]`` (pass a dict to read the count back; clean
    input is bit-identical under both modes).  Header/blank lines are
    never errors.
    """
    if on_error not in ("raise", "skip"):
        raise ValueError(f"on_error must be 'raise' or 'skip', got {on_error!r}")
    if fmt == "auto":
        fmt = sniff_format(path)
    parse = _PARSERS[fmt]
    check = _VALIDATORS[fmt]
    min_fields = 6 if fmt == "msr" else 4
    base = None
    if stats is not None:
        stats.setdefault("skipped_rows", 0)

    def flush(rows):
        nonlocal base
        ts, is_read, off, size, base = parse(rows, base)
        return {"arrival_us": ts, "is_read": is_read,
                "offset_bytes": off, "size_bytes": size}

    rows: list = []
    with _open_text(path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line or _is_header(line):
                continue
            fields = line.split(",")
            try:
                if len(fields) < min_fields:
                    raise ValueError(
                        f"{len(fields)} fields, {fmt!r} needs >= {min_fields}"
                    )
                check(fields)
            except ValueError as e:
                if on_error == "raise":
                    raise ValueError(
                        f"{path}:{lineno}: corrupted trace row "
                        f"{line[:80]!r} ({e})"
                    ) from None
                if stats is not None:
                    stats["skipped_rows"] += 1
                continue
            rows.append(fields)
            if len(rows) >= batch_requests:
                yield flush(rows)
                rows = []
    if rows:
        yield flush(rows)


def _normalize(batches: list, name: str) -> Dict[str, np.ndarray]:
    """Concatenate raw batches into the canonical byte-trace dict."""
    if not batches:
        raise ValueError(f"trace {name!r} has no parseable requests")
    ts = np.concatenate([b["arrival_us"] for b in batches])
    is_read = np.concatenate([b["is_read"] for b in batches])
    off = np.concatenate([b["offset_bytes"] for b in batches])
    size = np.maximum(1, np.concatenate([b["size_bytes"] for b in batches]))
    order = np.argsort(ts, kind="stable")  # some traces log out of order
    ts, is_read, off, size = ts[order], is_read[order], off[order], size[order]
    end = int((off + size).max())
    return {
        "name": name,
        "arrival_us": ts - ts[0],
        "is_read": is_read,
        "offset_bytes": off,
        "size_bytes": size,
        "footprint_bytes": end,
    }


def load_trace(
    path: str,
    fmt: str = "auto",
    name: str | None = None,
    compact: bool = True,
    batch_requests: int | None = None,
    on_error: str = "raise",
) -> Dict[str, np.ndarray]:
    """Parse a whole trace file to the canonical byte-trace dict.

    ``batch_requests=None`` parses the file in one pass (whole-file path);
    any integer routes through the streamed iterator — both are pinned
    identical by the test suite.  ``compact=True`` remaps the sparse LUN
    address space onto a dense footprint (:func:`compact_footprint`).
    ``on_error="skip"`` drops corrupted rows instead of raising; the drop
    count is returned as ``trace["skipped_rows"]`` (0 on clean input).
    """
    if fmt == "auto":
        fmt = sniff_format(path)
    if name is None:
        base = os.path.basename(path)
        if base.endswith(".gz"):
            base = base[:-3]
        name = os.path.splitext(base)[0]
    if batch_requests is None:
        batch_requests = 1 << 62  # one flush == whole file
    stats: dict = {}
    batches = list(iter_trace_csv(path, fmt, batch_requests,
                                  on_error=on_error, stats=stats))
    trace = _normalize(batches, name)
    if compact:
        trace = compact_footprint(trace)
    trace["skipped_rows"] = int(stats.get("skipped_rows", 0))
    if trace["skipped_rows"]:
        _report_skipped(path, trace["skipped_rows"])
    return trace


# files already warned about this process — silent drops should be loud,
# but once per file, not once per re-ingest of the same fixture
_WARNED_SKIPS: set = set()


def _report_skipped(path: str, count: int) -> None:
    """Surface silently-dropped rows: one warning per file per process
    (the loaded trace's ``skipped_rows`` counts every drop)."""
    if path not in _WARNED_SKIPS:
        _WARNED_SKIPS.add(path)
        warnings.warn(
            f"load_trace({path!r}): skipped {count} corrupted row"
            f"{'s' if count != 1 else ''} under on_error='skip'",
            stacklevel=3,
        )


def compact_footprint(
    trace: Dict[str, np.ndarray], align: int = 4096
) -> Dict[str, np.ndarray]:
    """Remap the touched address set onto a dense footprint.

    Touched byte ranges are rounded out to ``align`` boundaries and merged
    into maximal extents; each extent is then packed back-to-back.  The
    remap is monotone and gap-free inside an extent, so sequential runs,
    overlaps and re-references — everything the FTL's striping and the
    channel-skew analysis care about — are preserved; only never-touched
    gaps are dropped.  Offsets keep their intra-page byte remainder.
    """
    off = np.asarray(trace["offset_bytes"], np.int64)
    size = np.asarray(trace["size_bytes"], np.int64)
    s = off // align
    e = (off + size + align - 1) // align  # exclusive, align units
    order = np.argsort(s, kind="stable")
    s_s, e_s = s[order], e[order]
    # merged extents: a new extent starts where the running max end < start
    run_end = np.maximum.accumulate(e_s)
    new_ext = np.concatenate(([True], s_s[1:] > run_end[:-1]))
    ext_start = s_s[new_ext]
    ext_id = np.cumsum(new_ext) - 1
    # extent end = running max at the last member of each extent
    last = np.concatenate((np.flatnonzero(new_ext)[1:] - 1, [len(s_s) - 1]))
    ext_end = run_end[last]
    ext_len = ext_end - ext_start
    ext_base = np.concatenate(([0], np.cumsum(ext_len)[:-1]))
    # map each request through its extent
    req_ext = np.empty(len(off), np.int64)
    req_ext[order] = ext_id
    new_off = (ext_base[req_ext] + (s - ext_start[req_ext])) * align \
        + (np.asarray(trace["offset_bytes"], np.int64) % align)
    out = dict(trace)
    out["offset_bytes"] = new_off
    out["footprint_bytes"] = int(ext_len.sum()) * align
    return out


def write_msr_csv(trace: Dict[str, np.ndarray], path: str,
                  hostname: str = "anon") -> None:
    """Serialize a canonical byte trace as MSR-Cambridge CSV (the format
    :func:`load_trace` parses) — used to build anonymized test fixtures."""
    base_ft = 129_000_000_000_000_000  # arbitrary FILETIME epoch offset
    # ticks first, THEN the epoch offset, all in int64: FILETIME magnitudes
    # exceed float64's exact-integer range (ulp 16 at 1.3e17)
    ts = np.round(
        np.asarray(trace["arrival_us"], np.float64) * _FILETIME_PER_US
    ).astype(np.int64) + base_ft
    with open(path, "w") as f:
        for t, r, o, s in zip(ts, trace["is_read"], trace["offset_bytes"],
                              trace["size_bytes"]):
            typ = "Read" if r else "Write"
            f.write(f"{t},{hostname},0,{typ},{int(o)},{int(s)},0\n")


def ingest_file(path: str, fmt: str = "auto", name: str | None = None,
                compact: bool = True, on_error: str = "raise") -> str:
    """Load + register a trace for replay-by-name; returns the name under
    which ``bench.run_workload`` / the scenario engine can now replay it."""
    trace = load_trace(path, fmt=fmt, name=name, compact=compact,
                       on_error=on_error)
    register_trace(trace["name"], trace)
    return trace["name"]


# ---------------------------------------------------------------------------
# int64 window slicing — the ingestion half of the streaming engine
# ---------------------------------------------------------------------------


def arrival_ticks_i64(arrival_us: np.ndarray) -> np.ndarray:
    """Absolute int64 arrival ticks from float microseconds.

    The EXACT float64 op sequence of ``repro.ssd.config.us_to_ticks``
    (``ceil(us * 1e3 / TICK_NS)``) so window-rebased ticks reproduce what a
    monolithic decomposition would derive — the bit-exactness contract of
    the streaming engine hangs on this identity."""
    us = np.asarray(arrival_us, np.float64)
    return np.ceil(us * 1e3 / TICK_NS).astype(np.int64)


def iter_trace_windows(
    path: str,
    window_s: float = 10.0,
    fmt: str = "auto",
    batch_requests: int = 65536,
) -> Iterator[Dict[str, np.ndarray]]:
    """Stream a trace file as fixed-span time windows in bounded memory.

    Rides :func:`iter_trace_csv` (so ``.csv`` and ``.csv.gz`` both work)
    and regroups its batches by arrival time: each yielded dict carries the
    canonical raw columns for one ``window_s``-second span plus
    ``window_index``, ``base_ticks`` (the window's absolute tick origin)
    and ``arrival_ticks`` (int64, rebased to ``base_ticks`` — each value
    fits the int32 tick budget by construction).  Empty interior windows
    are yielded (zero-length arrays) so consumers can hold their
    window-count invariants; arrivals are assumed nondecreasing (MSR and
    blktrace logs are time-ordered after ingest normalization).
    """
    window_ticks = int(round(window_s * 1e9 / TICK_NS))
    if window_ticks <= 0:
        raise ValueError(f"window_s {window_s!r} must be positive")

    cols = ("arrival_us", "is_read", "offset_bytes", "size_bytes")
    empty = {k: np.zeros(0, np.float64 if k == "arrival_us" else np.int64)
             for k in cols}
    empty["is_read"] = np.zeros(0, bool)
    pend = dict(empty)  # joined not-yet-emitted rows (bounded: <1 window +
    pend_ticks = np.zeros(0, np.int64)  # 1 batch of rows at any time)
    widx = 0
    t0_us: float | None = None

    def cut_window():
        """Pop window ``widx``'s rows off the pending buffer."""
        nonlocal pend, pend_ticks, widx
        hi = (widx + 1) * window_ticks
        cut = int(np.searchsorted(pend_ticks, hi, side="left"))
        win = {"window_index": widx,
               "base_ticks": widx * window_ticks,
               "arrival_ticks": pend_ticks[:cut] - widx * window_ticks}
        for k in cols:
            win[k] = pend[k][:cut]
        pend = {k: pend[k][cut:] for k in cols}
        pend_ticks = pend_ticks[cut:]
        widx += 1
        return win

    for batch in iter_trace_csv(path, fmt, batch_requests):
        ts = np.asarray(batch["arrival_us"], np.float64)
        if len(ts) == 0:
            continue
        if t0_us is None:
            t0_us = float(ts[0])
        ts = ts - t0_us
        batch = dict(batch, arrival_us=ts)
        pend_ticks = np.concatenate((pend_ticks, arrival_ticks_i64(ts)))
        for k in cols:
            pend[k] = np.concatenate((pend[k], np.asarray(batch[k])))
        # every window ending at or before the last seen tick is complete
        # (arrivals are time-ordered), including empty interior windows
        while (widx + 1) * window_ticks <= int(pend_ticks[-1]):
            yield cut_window()
    if len(pend["arrival_us"]):
        yield cut_window()
