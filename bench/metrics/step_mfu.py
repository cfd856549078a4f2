"""The window batches' least whole-batch time (counts.step_counts at the
chip's peaks) over the traced window, in %."""


def read(ctx):
    least = ctx.least_s("step")
    if least is None or ctx.trace is None:
        return None
    return 100.0 * least / ctx.trace["window_s"]
