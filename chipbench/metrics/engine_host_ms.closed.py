"""Host ms per request that the engine spends padding the batch (stacking
the requests into one array) and reading the answers back, from the
engine's own per-batch phase samples in the window."""


def read(ctx):
    if not ctx.served:
        return None
    return (ctx.phase_ms["pad"] + ctx.phase_ms["readback"]) / ctx.served
