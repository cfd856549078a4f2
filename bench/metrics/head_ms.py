"""Device ms per window batch in the head's XLA module (_head_jit)."""


def read(ctx):
    return ctx.module_ms_per_batch("_head_jit")
