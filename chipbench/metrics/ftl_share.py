"""Share of host wall time spent turning traces into page transactions
(``bench.PERF["ftl_s"]``, host clock around the FTL)."""


def read(ctx):
    return 100.0 * ctx["perf"]["ftl_s"] / ctx["host_s"]
