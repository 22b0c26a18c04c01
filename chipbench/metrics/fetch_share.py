"""Share of host wall time copying groups' finished outputs back to the
host (``bench.PERF["fetch_s"]``: ``jax.device_get`` once the outputs are
ready; a part of ``exec_s``).  Nothing to read from a program without the
timer.

In a ``run.py --trace 1`` run the untraced sweeps read are mostly those
after the traced one.  In ``perf.static-long`` those run up to 2x faster
than the sweeps of a run that never traces, mostly in packing and in the
copy to the chip (PERF.md section 3), so there the share does not
describe the sweeps ``sim_txn_per_s`` times."""


def read(ctx):
    v = ctx["perf"].get("fetch_s")
    return None if v is None else 100.0 * v / ctx["host_s"]
