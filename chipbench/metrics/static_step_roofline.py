"""Share of the HBM roofline reached by the static step kernel: least bytes
(``roofline.least_bytes``) over its device time times peak bandwidth.
Nothing to read unless the trace holds the whole sweep whose groups the
least bytes count, and that sweep ran the kernel."""
from chipbench import roofline


def read(ctx):
    least = ctx["least_bytes"].get("static_step")
    kernel_s = ctx["trace"]["kernel_s"].get("static_step", 0.0)
    if not ctx["whole"] or not least or kernel_s <= 0:
        return None
    return roofline.share(least, kernel_s, ctx["peaks"]["hbm_bytes_per_s"])
