"""Share of host wall time inside the planner but outside device execution:
planning, table pre-gathers, stacking and scatter (``bench.PERF``
``sim_s`` minus ``exec_s``)."""


def read(ctx):
    p = ctx["perf"]
    return 100.0 * (p["sim_s"] - p["exec_s"]) / ctx["host_s"]
