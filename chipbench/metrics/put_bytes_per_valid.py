"""Bytes placed on the device per lane-step that carried a transaction
(``bench.PERF["put_bytes"]``, the ``nbytes`` of every argument
``sim._put_args`` placed, over ``scan_steps_valid``; exact counts).
Nothing to read from a program without the counter."""


def read(ctx):
    put = ctx["perf"].get("put_bytes")
    valid = ctx["perf"].get("scan_steps_valid", 0)
    return put / valid if put is not None and valid else None
