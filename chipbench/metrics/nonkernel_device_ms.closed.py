"""Device ms per request outside the Pallas GLCM kernels: the range
reduction, the input cast, symmetrize and normalize, and the feature tail
(its eigendecomposition among them)."""

from chipbench.metrics import ANY_KERNEL


def read(ctx):
    if ctx.trace is None or not ctx.records:
        return None
    ns = ctx.trace.busy_ns - ctx.trace.matching_ns(ANY_KERNEL) / ctx.trace.n_devices
    return ns / 1e6 / len(ctx.records)
