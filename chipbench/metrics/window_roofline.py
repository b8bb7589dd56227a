"""The window kernel's share of its roofline, in %: the least time of the
served requests' work (the kind's ``work``: every window's pairs voted,
the raw request in and the float32 answer out) over the device time of
the cell's Pallas GLCM kernels, which in the texture cell is the
window-features kernel alone."""

from chipbench.metrics import ANY_KERNEL, roofline_pct


def read(ctx):
    return roofline_pct(ctx, "window", ANY_KERNEL)
