"""Host ms per request of the engine's whole dispatch: the program's
``glcm.dispatch`` spans in the traced window."""


def read(ctx):
    ns = ctx.span_ns.get("glcm.dispatch")
    if not ns or not ctx.served:
        return None
    return ns / 1e6 / ctx.served
