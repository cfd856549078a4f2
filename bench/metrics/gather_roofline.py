"""The window batches' least gather time (counts.gather_counts at the
chip's peaks) over the serve_gather module's device time in the window, in %."""


def read(ctx):
    least, got = ctx.least_s("gather"), ctx.module_s("serve_gather")
    if least is None or got is None or got[1] != ctx.window_batches:
        return None
    return 100.0 * least / got[0]
