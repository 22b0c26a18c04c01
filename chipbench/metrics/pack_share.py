"""Share of host wall time packing groups' arguments: stacking lane
tables and transactions, pregathering the mask tables
(``bench.PERF["pack_s"]``, from ``sweep_plan._dispatch``'s entry until
``sim._run_compiled`` is entered).  Nothing to read from a program
without the timer.

In a ``run.py --trace 1`` run the untraced sweeps read are mostly those
after the traced one.  In ``perf.static-long`` those run up to 2x faster
than the sweeps of a run that never traces, mostly in packing and in the
copy to the chip (PERF.md section 3), so there the share does not
describe the sweeps ``sim_txn_per_s`` times."""


def read(ctx):
    v = ctx["perf"].get("pack_s")
    return None if v is None else 100.0 * v / ctx["host_s"]
