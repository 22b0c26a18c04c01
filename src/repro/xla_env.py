"""Process environment for the simulator's entry points, set before JAX
initializes.

Every entry point (``benchmarks/run.py``, ``chip_smoke.py``, the test
conftest) calls :func:`configure` before anything imports jax: jax reads
these variables once, on first use.  This module is deliberately free of
jax imports (``repro`` is a namespace package, so importing it pulls in
nothing else).

* Host device count: ``--xla_force_host_platform_device_count`` gives the
  CPU backend one device per core, so the sweep planner can shard lane
  groups across them.  It only shapes the CPU platform; on an accelerator
  the planner shards over the accelerator's devices.
* Compile cache: JAX's persistent compilation cache, in exactly one
  place.  A ``JAX_COMPILATION_CACHE_DIR`` set by the caller is used as it
  is; otherwise the cache lives at :data:`CACHE_DIR`, a fixed path inside
  the checkout (git-ignored), so every process that runs this checkout —
  from any working directory — finds what the previous one compiled.
"""
from __future__ import annotations

import os

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))),
    ".cache", "jax",
)


def configure(device_count: int | str | None = None) -> None:
    """Append the host device-count flag to ``XLA_FLAGS`` and place JAX's
    persistent compilation cache, each only where the caller/user has not
    already chosen.  ``device_count`` defaults to the ``BENCH_DEVICES``
    env var, then the machine's core count."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        n = device_count or os.environ.get(
            "BENCH_DEVICES", str(os.cpu_count() or 1)
        )
        flags = f"{flags} --xla_force_host_platform_device_count={n}"
    os.environ["XLA_FLAGS"] = flags.strip()

    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", CACHE_DIR)
    # cache every entry: the simulator's many small executables are
    # individually below jax's default 1s/small-entry thresholds
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
