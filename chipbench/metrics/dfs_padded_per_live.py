"""DFS while-loop iterations the batched scout groups scheduled, times the
lanes stepped in lockstep with them, per DFS step of a lane's own walks,
every try and raced scout (``bench.PERF["dfs_steps_padded"] /
["dfs_steps_live"]``; exact counts): 1 for a lone lane, more as lanes
wait on each other in lockstep or pad the group.  Nothing to read without a scout group, or from a program without the
counters."""


def read(ctx):
    padded = ctx["perf"].get("dfs_steps_padded")
    live = ctx["perf"].get("dfs_steps_live", 0)
    return padded / live if padded is not None and live else None
