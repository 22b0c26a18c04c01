"""Backend selection for the Pallas kernels.

Two independent knobs live here:

* ``default_interpret`` — should a ``pl.pallas_call`` run in interpreter
  mode?  Pallas has no CPU compiler, so on the CPU backend the only way to
  execute a kernel is ``interpret=True`` (the kernel body is traced into
  the surrounding XLA program).  On GPU/TPU the compiled path is the whole
  point.  Callers may force either mode explicitly; otherwise we ask JAX.

* ``resolve_lane_backend`` lives in ``ssd.sim`` (it feeds executable-cache
  keys); this module only answers the interpret question so the kernels
  package stays free of simulator imports.
"""
from __future__ import annotations

import os

_ACCELERATORS = ("gpu", "tpu", "cuda", "rocm")


def default_interpret(override: bool | None = None) -> bool:
    """Pick Pallas interpret mode.

    Priority: explicit ``override`` > ``REPRO_PALLAS_INTERPRET`` env var
    ("0"/"1") > the actual JAX backend (interpret everywhere except a real
    accelerator).  Interpret mode on an accelerator is refused, whichever
    way it is asked for: it would run the kernel's math as plain XLA ops
    and hide the kernel from the device.
    """
    import jax

    on_accel = jax.default_backend() in _ACCELERATORS
    if override is None:
        env = os.environ.get("REPRO_PALLAS_INTERPRET")
        if env is not None and env != "":
            override = env not in ("0", "false", "False")
    if override is None:
        return not on_accel
    if override and on_accel:
        raise ValueError(
            "Pallas interpret mode requested on a "
            f"{jax.default_backend()} device; kernels run compiled there")
    return bool(override)
