"""The window batches' least time to sum the chips' pooled partials (the
bytes each chip must receive, ``ici.reduce_bytes``, at the chip's published
ICI rate) over the _reduce_pooled_jit module's device time, in %."""

from bench import ici


def read(ctx):
    got = ctx.module_s("_reduce_pooled_jit")
    if ctx.peaks is None or got is None or got[1] != ctx.window_batches:
        return None
    least = sum(ici.least_reduce_s(ids.shape[0], ids.shape[1], ctx.model.dim,
                                   ctx.chips, ctx.peaks)
                for ids in ctx.window_ids)
    return 100.0 * least / got[0]
