"""Samples served in the window over its wall time (overlap mode)."""


def read(ctx):
    return ctx.result["served"] / ctx.result["wall_s"]
