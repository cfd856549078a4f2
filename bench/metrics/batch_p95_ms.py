"""95th percentile of every window batch's latency, in ms (sequential
mode: prefetch, translate, upload, gather, head, block_until_ready)."""

import numpy as np


def read(ctx):
    return float(np.percentile(ctx.result["latencies_s"], 95)) * 1e3
