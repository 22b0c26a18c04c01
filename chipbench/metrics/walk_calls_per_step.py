"""Rounds of the batched scout runner's retry loop per scan step: the walk
calls a scan step waits on, one after another (``bench.PERF
["scout_walk_calls"] / ["scout_scan_steps"]``, each summed over the scout
groups' shards; exact counts).  1 when every transaction of a step
reserves within the tries one call walks; above 1, the lockstep that
remains.  Nothing to read without a scout group, or from a program without
the counters."""


def read(ctx):
    calls = ctx["perf"].get("scout_walk_calls")
    steps = ctx["perf"].get("scout_scan_steps", 0)
    return calls / steps if calls is not None and steps else None
