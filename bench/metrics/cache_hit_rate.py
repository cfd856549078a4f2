"""Prefetch-cache hits over accesses (CacheStats), in %, over the
window call's batches."""


def read(ctx):
    return 100.0 * ctx.result["hit_rate"]
