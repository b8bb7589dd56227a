"""Device ms per request of the fused multi-offset image kernel."""

from chipbench.metrics import kernel_ms


def read(ctx):
    return kernel_ms(ctx, "fused")
