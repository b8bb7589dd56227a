"""Device ms per request of the fused multi-offset image kernel."""

from chipbench.metrics import KERNELS, kernel_ms


def read(ctx):
    return kernel_ms(ctx, KERNELS["fused"])
