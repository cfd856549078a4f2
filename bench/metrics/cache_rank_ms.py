"""Host ms per window batch in the obs spans cache_rank: the prefetch
schedulers' ranking phase over every table (bincount, argsort, filter)."""


def read(ctx):
    return ctx.span_ms_per_batch({"cache_rank"})
