"""Share of host wall time turning lane outputs into results: per-lane
slicing, the merge and scatter of channel-decomposed lanes and
``sim._finish_result`` (``bench.PERF["unpack_s"]``).  Nothing to read
from a program without the timer.

In a ``run.py --trace 1`` run the untraced sweeps read are mostly those
after the traced one.  In ``perf.static-long`` those run up to 2x faster
than the sweeps of a run that never traces, mostly in packing and in the
copy to the chip (PERF.md section 3), so there the share does not
describe the sweeps ``sim_txn_per_s`` times."""


def read(ctx):
    v = ctx["perf"].get("unpack_s")
    return None if v is None else 100.0 * v / ctx["host_s"]
