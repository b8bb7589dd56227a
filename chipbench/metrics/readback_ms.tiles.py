"""Host ms per request that the engine spends reading answers back from
the device: the program's ``glcm.readback`` spans in the traced window."""


def read(ctx):
    ns = ctx.span_ns.get("glcm.readback")
    if not ns or not ctx.served:
        return None
    return ns / 1e6 / ctx.served
