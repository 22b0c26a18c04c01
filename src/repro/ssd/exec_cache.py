"""Persistent AOT executable cache: never pay the same compile twice.

The quick preset spends ~13 s of its ~36 s compiling executables that are
byte-for-byte identical run over run (the programs are design-agnostic and
keyed on geometry/capacity/cost class — see ``sim._build_group_fn``), so a
warm ``benchmarks/run.py`` was still paying the full cold-compile tax every
process.  This module serializes compiled executables with
``jax.experimental.serialize_executable`` (true AOT: loading skips
tracing, lowering AND XLA compilation) into a versioned on-disk store.
It is an opt-in alternative to JAX's native persistent compilation cache
(which ``repro.xla_env`` places by default, and which still re-traces and
re-lowers, but skips the XLA backend compile); the two never run together.

Store layout
    ``$REPRO_XC_DIR/<digest>.xc`` — one file per executable, written
    atomically (tmp + rename).  The digest is
    ``sha256(version salt || logical key)`` where the *version salt*
    covers everything that can change the lowered HLO or the produced
    machine code without showing up in the logical key:

    * ``jax.__version__`` + ``jaxlib.__version__``,
    * the XLA backend platform and its runtime version,
    * ``XLA_FLAGS`` (device count, ...),
    * the *source digest* of the modules that define the programs
      (``ssd/sim.py``, ``ssd/designs.py``, ``ssd/config.py``,
      ``core/scout.py``, ``core/topology.py``, ``core/routing.py``),
    * ``REPRO_XC_SALT`` (manual invalidation / tests).

    Keying on the source digest instead of the lowered HLO text is a
    deliberate deviation from "digest the lowering": it is a conservative
    over-approximation (a comment edit invalidates the cache; nothing that
    changes the HLO survives it) and it keeps the warm path free of the
    ~0.1-1 s tracing+lowering cost per program that digesting the HLO
    would re-introduce — the whole point of the AOT tier.

Failure model
    Every disk/deserialize problem — corrupted payload, truncated file,
    version-skewed pickle, missing device topology — degrades to a cache
    miss (the caller compiles) and bumps ``STATS["errors"]``; the broken
    entry is deleted so it cannot fail twice.  The cache is disabled when
    ``REPRO_XC_DIR`` is unset/empty, which is the default: a user opts in
    by exporting it, with JAX's persistent cache turned off
    (``JAX_ENABLE_COMPILATION_CACHE=false``; see :func:`cache_dir`).
"""
from __future__ import annotations

import functools
import hashlib
import os
import pickle
import tempfile

__all__ = ["cache_dir", "has", "lookup", "store", "flush", "STATS",
           "reset_stats"]

# process-wide telemetry, mirrored into bench.PERF by the sweep planner.
# ``tombstones``: programs XLA:CPU cannot round-trip (a deserialize bug for
# some program shapes — e.g. "Symbols not found: main.N_spmd"); the store
# verifies every entry by reloading it once at store time and persists a
# tombstone instead, so warm runs take the recompile deterministically
# rather than erroring/deleting/re-storing forever.
STATS = {"hits": 0, "misses": 0, "errors": 0, "stores": 0, "tombstones": 0}

_FORMAT = 2  # bump to orphan every existing entry

# modules whose source participates in the version salt: everything that
# can trace INTO a stored program (see docstring).  Err on the side of
# including — a spurious invalidation costs one recompile, a missing
# module serves stale machine code after an edit.
_PROGRAM_SOURCES = (
    "repro.ssd.sim",
    "repro.ssd.designs",
    "repro.ssd.config",
    "repro.core.scout",
    "repro.core.topology",
    "repro.core.routing",
    "repro.core.rng",
    "repro.kernels.onehot",
)


def reset_stats() -> None:
    for k in STATS:
        STATS[k] = 0


def cache_dir() -> str | None:
    """The store directory, or None when the store is disabled.

    Disabled unless ``REPRO_XC_DIR`` names a directory, and disabled while
    JAX's persistent compilation cache is on: an executable JAX loaded
    from its own cache does not survive this store's round trip on
    XLA:CPU (it deserializes, then fails at run time with "Function ...
    not found"), so the two caches exclude each other and JAX's wins.
    """
    d = os.environ.get("REPRO_XC_DIR", "")
    if not d:
        return None
    import jax

    if (jax.config.jax_enable_compilation_cache
            and jax.config.jax_compilation_cache_dir):
        return None
    return d


@functools.lru_cache(maxsize=None)
def _source_digest() -> str:
    import importlib

    h = hashlib.sha256()
    for mod in _PROGRAM_SOURCES:
        path = importlib.import_module(mod).__file__
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


@functools.lru_cache(maxsize=None)
def _version_salt() -> bytes:
    import jax
    import jaxlib

    dev = jax.devices()[0]
    parts = (
        f"format={_FORMAT}",
        f"jax={jax.__version__}",
        f"jaxlib={jaxlib.__version__}",
        f"platform={dev.platform}",
        f"platform_version={getattr(dev.client, 'platform_version', '')}",
        f"devices={len(jax.devices())}",
        f"xla_flags={os.environ.get('XLA_FLAGS', '')}",
        f"sources={_source_digest()}",
        f"salt={os.environ.get('REPRO_XC_SALT', '')}",
    )
    return "|".join(parts).encode()


def entry_digest(logical_key: tuple) -> str:
    """Stable digest of (version salt, logical executable key)."""
    h = hashlib.sha256(_version_salt())
    h.update(repr(logical_key).encode())
    return h.hexdigest()


def _entry_path(digest: str) -> str:
    return os.path.join(cache_dir(), digest + ".xc")


def has(logical_key: tuple) -> bool:
    """Cheap existence probe (no load, no counters) — the planner uses it
    to decide whether a key needs main-thread lowering or just a worker
    deserialize."""
    return (cache_dir() is not None
            and os.path.exists(_entry_path(entry_digest(logical_key))))


def lookup(logical_key: tuple, devices=None):
    """Load a compiled executable for ``logical_key``, or None.

    ``devices`` are the devices the program was compiled for, in order
    (None: every device of the backend).  The loaded executable expects
    its inputs placed exactly there — a one-device program loaded onto
    every device would demand one shard per device.

    Any failure (absent, corrupted, version-mismatched, wrong topology)
    returns None so the caller falls back to compiling; corruption also
    deletes the entry and counts in ``STATS["errors"]``.
    """
    if cache_dir() is None:
        return None
    path = _entry_path(entry_digest(logical_key))
    if not os.path.exists(path):
        STATS["misses"] += 1
        return None
    try:
        from jax.experimental import serialize_executable as se

        with open(path, "rb") as f:
            entry = pickle.load(f)
        if isinstance(entry, dict) and entry.get("tombstone"):
            # known-unserializable program: deterministic recompile
            STATS["tombstones"] += 1
            STATS["misses"] += 1
            return None
        payload, in_tree, out_tree = entry
        compiled = se.deserialize_and_load(payload, in_tree, out_tree,
                                           execution_devices=devices)
    except Exception:  # noqa: BLE001 — any breakage degrades to a miss
        STATS["errors"] += 1
        STATS["misses"] += 1
        try:  # tombstone the entry: if the program is one XLA:CPU cannot
            # round-trip (see STATS docstring), later runs take the
            # recompile deterministically instead of re-erroring; a
            # genuinely corrupted entry loses nothing either way
            _write_entry(path, pickle.dumps({"tombstone": _FORMAT}))
        except OSError:
            pass
        return None
    STATS["hits"] += 1
    return compiled


def _write_entry(path: str, blob: bytes) -> None:
    d = cache_dir()
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)
    except BaseException:
        os.unlink(tmp)
        raise


def _store_now(logical_key: tuple, compiled, devices) -> None:
    try:
        from jax.experimental import serialize_executable as se

        path = _entry_path(entry_digest(logical_key))
        if os.path.exists(path):  # racing store of the same key
            return
        payload, in_tree, out_tree = se.serialize(compiled)
        # verify the round trip BEFORE committing: XLA:CPU serialization
        # is nondeterministically broken for some program/process states
        # ("Symbols not found: main.N[_spmd]" — correlates with the
        # process's module counter; long-lived test sessions hit it).
        # A failing entry becomes a tombstone: every later run recompiles
        # it deterministically instead of erroring.  The compile server
        # (a fresh short-lived process where serialization is reliable)
        # opts out via REPRO_XC_VERIFY=0 — its rare bad entry is caught
        # at load time by the parent's error->tombstone fallback instead.
        if os.environ.get("REPRO_XC_VERIFY", "1") != "0":
            try:
                se.deserialize_and_load(payload, in_tree, out_tree,
                                        execution_devices=devices)
            except Exception:  # noqa: BLE001
                _write_entry(path, pickle.dumps({"tombstone": _FORMAT}))
                STATS["tombstones"] += 1
                return
        _write_entry(path, pickle.dumps((payload, in_tree, out_tree)))
    except Exception:  # noqa: BLE001
        STATS["errors"] += 1
        return
    STATS["stores"] += 1


_STORE_POOL = None
_PENDING = []


def store(logical_key: tuple, compiled, devices=None) -> None:
    """Queue ``compiled`` for serialization under ``logical_key``
    (``devices`` as in :func:`lookup`).

    Stores run on a single background writer (serialize + the round-trip
    verification are not free, and the compile workers should be
    compiling); failures are swallowed — a cache must never take the run
    down with it.  :func:`flush` joins the queue (tests; atexit).
    """
    if cache_dir() is None:
        return
    global _STORE_POOL
    if _STORE_POOL is None:
        import atexit
        import concurrent.futures

        _STORE_POOL = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="xc-store"
        )
        atexit.register(flush)
    _PENDING.append(_STORE_POOL.submit(_store_now, logical_key, compiled,
                                       devices))


def flush() -> None:
    """Wait for queued stores to hit disk."""
    while _PENDING:
        _PENDING.pop().result()
