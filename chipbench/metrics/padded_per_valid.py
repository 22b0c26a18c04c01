"""Lane-steps the planner scheduled per lane-step that carried a
transaction (``bench.PERF`` scan-step counters; an exact count)."""


def read(ctx):
    valid = ctx["perf"].get("scan_steps_valid", 0)
    return ctx["perf"]["scan_steps_padded"] / valid if valid else None
