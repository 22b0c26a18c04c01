"""Warm-path executable cache: parity, fallback, and telemetry.

The load-bearing guarantees of the persistent AOT store
(``repro.ssd.exec_cache``):

* results served by deserialized executables are bit-identical to
  freshly-compiled ones (in-process and across processes);
* corrupted or version-mismatched entries degrade to a compile — never a
  crash — and the miss/error counters say so;
* the store is an optimization, not a dependency: disabling it changes
  nothing but wall-clock.
"""
import hashlib
import os
import pickle
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.ssd import bench, exec_cache, simulate_sweep
from repro.ssd import sim as S

PARITY_FIELDS = ("completion", "wait", "conflict", "hops", "tries",
                 "misroutes")
DESIGNS_MIX = ("baseline", "pnssd", "nossd", "venice")


def _digest(sweep) -> str:
    h = hashlib.sha1()
    for lane in sweep:
        for f in PARITY_FIELDS:
            h.update(np.ascontiguousarray(getattr(lane, f)).tobytes())
    return h.hexdigest()


@pytest.fixture()
def xc_dir(tmp_path, monkeypatch, no_jax_cache):
    """A fresh store for this test only."""
    d = str(tmp_path / "xc")
    monkeypatch.setenv("REPRO_XC_DIR", d)
    exec_cache.flush()  # other tests' queued stores keep out of STATS
    S.clear_exec_cache()
    exec_cache.reset_stats()
    yield d
    S.clear_exec_cache()
    exec_cache.reset_stats()


def test_store_yields_to_jax_cache(tmp_path, monkeypatch):
    """With JAX's persistent cache on (the default), the opted-in store
    stays disabled: the two caches exclude each other."""
    monkeypatch.setenv("REPRO_XC_DIR", str(tmp_path / "xc"))
    assert jax.config.jax_enable_compilation_cache
    assert exec_cache.cache_dir() is None


def test_store_roundtrip_bit_identical(tiny_cfg, tiny_txns, xc_dir):
    """cold compile+store -> drop in-process cache -> disk load: the
    loaded executables must reproduce every output bit.

    The store verifies each entry's round trip before committing and
    tombstones programs XLA:CPU cannot re-load (nondeterministic,
    process-state-dependent — see exec_cache), so the invariants are:
    every program either stored or tombstoned; every STORED program loads
    (hits == prior stores, zero errors); outputs bit-identical
    regardless."""
    cold = simulate_sweep(tiny_cfg, tiny_txns, DESIGNS_MIX, seeds=11)
    exec_cache.flush()
    stored = exec_cache.STATS["stores"]
    assert stored + exec_cache.STATS["tombstones"] > 0
    assert os.listdir(xc_dir)

    S.clear_exec_cache()  # force the disk path
    warm = simulate_sweep(tiny_cfg, tiny_txns, DESIGNS_MIX, seeds=11)
    assert exec_cache.STATS["hits"] == stored, exec_cache.STATS
    assert exec_cache.STATS["errors"] == 0, exec_cache.STATS
    assert _digest(cold) == _digest(warm)
    assert bench.PERF["xc_hits"] == exec_cache.STATS["hits"]


def test_corrupted_entries_fall_back_to_compile(tiny_cfg, tiny_txns,
                                                xc_dir):
    """Garbage payloads must count as errors and recompile, bit-exact."""
    ref = simulate_sweep(tiny_cfg, tiny_txns, DESIGNS_MIX, seeds=11)
    exec_cache.flush()
    entries = [os.path.join(xc_dir, f) for f in os.listdir(xc_dir)
               if f.endswith(".xc")]
    assert entries
    for path in entries:
        with open(path, "wb") as f:
            f.write(b"\x00garbage\xff" * 32)

    S.clear_exec_cache()
    exec_cache.reset_stats()
    again = simulate_sweep(tiny_cfg, tiny_txns, DESIGNS_MIX, seeds=11)
    assert _digest(again) == _digest(ref)
    assert exec_cache.STATS["errors"] > 0
    assert exec_cache.STATS["hits"] == 0
    # corrupted entries were tombstoned: the NEXT pass recompiles
    # deterministically (a miss, not another error)
    S.clear_exec_cache()
    exec_cache.reset_stats()
    third = simulate_sweep(tiny_cfg, tiny_txns, DESIGNS_MIX, seeds=11)
    assert _digest(third) == _digest(ref)
    assert exec_cache.STATS["errors"] == 0
    assert exec_cache.STATS["tombstones"] > 0


def test_version_salt_invalidates(tiny_cfg, tiny_txns, xc_dir,
                                  monkeypatch):
    """A changed version salt (stand-in for a jaxlib/XLA-flag/source
    change) must miss — never serve a stale executable."""
    simulate_sweep(tiny_cfg, tiny_txns, ("baseline",), seeds=1)
    exec_cache.flush()
    assert exec_cache.STATS["stores"] + exec_cache.STATS["tombstones"] > 0

    monkeypatch.setenv("REPRO_XC_SALT", "other-toolchain")
    exec_cache._version_salt.cache_clear()
    S.clear_exec_cache()
    exec_cache.reset_stats()
    simulate_sweep(tiny_cfg, tiny_txns, ("baseline",), seeds=1)
    exec_cache.flush()
    assert exec_cache.STATS["hits"] == 0
    assert exec_cache.STATS["misses"] > 0
    monkeypatch.delenv("REPRO_XC_SALT")
    exec_cache._version_salt.cache_clear()


def test_disabled_store_is_inert(tiny_cfg, tiny_txns, monkeypatch):
    monkeypatch.setenv("REPRO_XC_DIR", "")
    exec_cache.flush()
    S.clear_exec_cache()
    exec_cache.reset_stats()
    simulate_sweep(tiny_cfg, tiny_txns, ("baseline",), seeds=1)
    exec_cache.flush()
    assert exec_cache.STATS == {"hits": 0, "misses": 0, "errors": 0,
                                "stores": 0, "tombstones": 0}
    S.clear_exec_cache()


@pytest.mark.slow
def test_warm_subprocess_digest_and_speedup_parity(tmp_path):
    """Fresh process with an empty store vs fresh process with the
    populated store: identical digests AND identical speedups, with the
    warm run actually loading executables instead of compiling."""
    xc = str(tmp_path / "xc")
    script = r"""
import json, hashlib, sys
import numpy as np
from repro.ssd import bench, exec_cache, decompose_trace, perf_optimized, simulate_sweep
from repro.traces.generator import gen_trace, to_pages

cfg = perf_optimized(rows=2, cols=2, pages_per_block=64)
tr = gen_trace("src2_1", 60, seed=3)
tr = dict(tr); tr["arrival_us"] = tr["arrival_us"] / 16.0
pages = to_pages(tr, cfg.page_bytes)
txns = decompose_trace(cfg, pages, footprint_pages=int(pages["footprint_pages"]))
designs = ("baseline", "pssd", "venice", "ideal")
sweep = simulate_sweep(cfg, txns, designs, seeds=5)
h = hashlib.sha1()
for lane in sweep:
    for f in ("completion", "wait", "conflict", "hops", "tries", "misroutes"):
        h.update(np.ascontiguousarray(getattr(lane, f)).tobytes())
base = dict(zip(designs, sweep))
speedups = {d: base["baseline"].exec_ticks / max(base[d].exec_ticks, 1)
            for d in designs}
exec_cache.flush()
print("RESULT", json.dumps({
    "digest": h.hexdigest(), "speedups": speedups,
    "stats": exec_cache.STATS}))
"""
    env = dict(os.environ, REPRO_XC_DIR=xc, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false")

    def run_once():
        out = subprocess.run([sys.executable, "-c", script], env=env,
                             capture_output=True, text=True, timeout=560)
        assert out.returncode == 0, out.stderr[-2000:]
        line = [l for l in out.stdout.splitlines()
                if l.startswith("RESULT")][0]
        import json

        return json.loads(line.split(" ", 1)[1])

    cold = run_once()
    warm = run_once()
    assert cold["digest"] == warm["digest"]
    assert cold["speedups"] == warm["speedups"]
    assert cold["stats"]["stores"] > 0 and cold["stats"]["hits"] == 0
    assert warm["stats"]["hits"] > 0 and warm["stats"]["stores"] == 0
    assert warm["stats"]["errors"] == 0


def test_entry_digest_covers_logical_key(xc_dir):
    k1 = ("lane", (2, 2, 2, 2, 1), 1024, 2, 1, False, (None,) * 12, 2)
    k2 = ("lane", (2, 2, 2, 2, 1), 1024, 2, 1, True, (None,) * 12, 2)
    assert exec_cache.entry_digest(k1) != exec_cache.entry_digest(k2)
    assert exec_cache.entry_digest(k1) == exec_cache.entry_digest(k1)


def test_stale_version_entry_degrades_to_compile(xc_dir, monkeypatch):
    """Version skew (ISSUE 8): an entry planted under a stale jaxlib
    salt is invisible to the current toolchain — a plain miss, never a
    crash; a stale payload sitting AT the current digest (salt collision
    / partial upgrade) errors exactly once, is tombstoned, and every
    later lookup takes the deterministic recompile path."""
    key = ("lane", "stale-jaxlib-probe")
    monkeypatch.setenv("REPRO_XC_SALT", "jaxlib=0.0.0-stale")
    exec_cache._version_salt.cache_clear()
    stale_path = exec_cache._entry_path(exec_cache.entry_digest(key))
    os.makedirs(xc_dir, exist_ok=True)
    blob = pickle.dumps(("not-an-executable", None, None))
    with open(stale_path, "wb") as f:
        f.write(blob)
    monkeypatch.delenv("REPRO_XC_SALT")
    exec_cache._version_salt.cache_clear()
    # the stale entry lives under a different digest: clean miss
    assert exec_cache.entry_digest(key) not in os.path.basename(stale_path)
    assert not exec_cache.has(key)
    assert exec_cache.lookup(key) is None
    assert exec_cache.STATS == {"hits": 0, "misses": 1, "errors": 0,
                                "stores": 0, "tombstones": 0}
    # same payload at the CURRENT digest: one error, then tombstone
    with open(exec_cache._entry_path(exec_cache.entry_digest(key)),
              "wb") as f:
        f.write(blob)
    assert exec_cache.lookup(key) is None
    assert exec_cache.STATS["errors"] == 1
    assert exec_cache.lookup(key) is None
    assert exec_cache.STATS["tombstones"] == 1
    assert exec_cache.STATS["errors"] == 1  # tombstone, not a re-error
