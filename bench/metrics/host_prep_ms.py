"""Host ms per window batch in the obs spans prefetch + pack + h2d."""


def read(ctx):
    return ctx.span_ms_per_batch({"prefetch", "pack", "h2d"})
