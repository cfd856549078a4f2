"""Seconds of the run before the window: process start to the end of the
window call, less the window."""


def read(ctx):
    return ctx.setup_s
