"""Host ms per request that the engine spends in its host→device copy: the
program's ``glcm.h2d`` spans in the traced window (the copy of the batch,
the kernel enqueued behind it, until the input is on the device)."""


def read(ctx):
    ns = ctx.span_ns.get("glcm.h2d")
    if not ns or not ctx.served:
        return None
    return ns / 1e6 / ctx.served
