"""Device ms per window batch in the module that sums the chips' pooled
partials (_reduce_pooled_jit)."""


def read(ctx):
    return ctx.module_ms_per_batch("_reduce_pooled_jit")
