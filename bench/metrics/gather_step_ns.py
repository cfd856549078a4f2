"""Device ns per grid step of the gather megakernel: the serve_gather
module's device time in the window over the grid steps that the program's
dispatch spans of the window batches carry (``grid_steps``, pad bags
included).  None unless each of those dispatches has one module run."""


def read(ctx):
    steps = [s[3].get("grid_steps") for s in ctx.spans
             if s[0] == "dispatch" and s[3].get("batch", -1) >= 1]
    got = ctx.module_s("serve_gather")
    if not steps or None in steps or got is None or got[1] != len(steps):
        return None
    return got[0] * 1e9 / sum(steps)
