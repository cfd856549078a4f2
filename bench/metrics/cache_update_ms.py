"""Host ms per window batch in the obs spans cache_update: the prefetch
schedulers' residency phase over every table (keep, evict, stage)."""


def read(ctx):
    return ctx.span_ms_per_batch({"cache_update"})
