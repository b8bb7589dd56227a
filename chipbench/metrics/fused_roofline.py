"""The fused image kernel's share of its roofline, in %."""

from chipbench.metrics import KERNELS, roofline_pct


def read(ctx):
    return roofline_pct(ctx, "fused", KERNELS["fused"])
