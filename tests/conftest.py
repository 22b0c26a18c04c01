"""Shared fixtures: tiny-geometry configs keep tier-1 JIT under control.

The full 8x8 mesh compiles a large scan program; most behavioural properties
hold on a 2x2 mesh with short traces, which compiles in seconds.  Heavy
full-geometry sweeps are marked ``@pytest.mark.slow`` and excluded from the
default run (see pytest.ini).
"""
# Two virtual XLA host devices so the whole tier runs against the sweep
# planner's sharded (shard_map) execution path — the multi-core layout the
# benchmarks use.  The single-device environment is covered by the
# subprocess parity test in tests/test_sweep_plan.py.  MUST run before any
# jax import: jax reads these on first init.
from repro.xla_env import configure as _configure_xla

_configure_xla(device_count=2)

import numpy as np
import pytest

from repro.ssd import decompose_trace, perf_optimized
from repro.traces.generator import gen_trace, to_pages


@pytest.fixture()
def no_jax_cache():
    """JAX's persistent compilation cache off for one test: a compile for
    a TPU that is not attached cannot be read back.  JAX's in-memory
    caches are dropped first: an executable an earlier test loaded from
    the persistent cache would otherwise be handed out again."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    jax.clear_caches()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


@pytest.fixture(scope="session")
def tiny_cfg():
    """2x2 mesh (4 chips, 8 planes) — smallest geometry with path diversity."""
    return perf_optimized(rows=2, cols=2, pages_per_block=64)


@pytest.fixture(scope="session")
def tiny_txns(tiny_cfg):
    """A short saturating trace decomposed for the tiny geometry."""
    tr = gen_trace("src2_1", 60, seed=3)
    tr = dict(tr)
    tr["arrival_us"] = tr["arrival_us"] / 16.0  # intensify into conflicts
    pages = to_pages(tr, tiny_cfg.page_bytes)
    return decompose_trace(
        tiny_cfg, pages, footprint_pages=int(pages["footprint_pages"])
    )


def mk_txns(arrival_us, kinds, planes, nbytes, cfg):
    """Hand-built transaction dict (mirrors repro.ssd.ftl's layout)."""
    from repro.ssd.config import us_to_ticks

    n = len(arrival_us)
    planes = np.asarray(planes, np.int64)
    chips = planes // (cfg.dies_per_chip * cfg.planes_per_die)
    return {
        "arrival": np.array([us_to_ticks(a) for a in arrival_us], np.int64),
        "kind": np.asarray(kinds, np.int64),
        "plane": planes,
        "node": chips,
        "row": chips // cfg.cols,
        "nbytes": np.asarray(nbytes, np.int64),
        "req": np.arange(n, dtype=np.int64),
    }
