"""Device ms per window batch in the serve_gather XLA module."""


def read(ctx):
    return ctx.module_ms_per_batch("serve_gather")
