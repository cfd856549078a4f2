"""Lower bounds on the work of one served batch, from the configuration's
shapes and the batch's own ids.

Each count is one that no implementation can beat: every distinct row a
batch touches is read once, every distinct id is rebuilt once, and nothing
is counted that an implementation could skip.  A share of a roofline made
from these counts can pass 100% only by reusing rows that a cache kept from
an earlier batch, which at most a few MiB of slots could hold.
"""

from __future__ import annotations

import numpy as np

F32 = 4     # bytes of a stored element (tables, weights, activations)
IDX = 4     # bytes of an int32 id


def _distinct(keys: np.ndarray) -> int:
    return int(np.unique(keys).size)


def _per_table(x: np.ndarray, span: int) -> np.ndarray:
    """(B, T, K) ids of tables that each span ``span`` -> globally unique."""
    t = np.arange(x.shape[1], dtype=np.int64)[None, :, None]
    return x.astype(np.int64) + t * span


def gather_counts(idx: np.ndarray, m) -> dict:
    """FLOPs and HBM bytes of the embedding layer for (B, T, K) ids ``idx``.

    Bytes: the distinct rows of every subtable the batch touches, the ids
    read and the pooled (B, T, dim) result written.  FLOPs: each distinct id
    rebuilt once and the pooling adds; a dense row is read, not rebuilt.
    """
    b, t, k = idx.shape
    ids = _per_table(idx, m.vocab_per_table)
    pool_flops = b * t * (k - 1) * m.dim
    io_bytes = idx.size * IDX + b * t * m.dim * F32
    if m.kind == "dense":
        row_bytes = _distinct(ids) * m.dim * F32
        flops = pool_flops
    elif m.kind == "qr":
        q = _per_table(idx // m.collision, m.q_rows)
        r = _per_table(idx % m.collision, m.collision)
        row_bytes = (_distinct(q) + _distinct(r)) * m.dim * F32
        flops = _distinct(ids) * m.dim + pool_flops
    elif m.kind == "tt":
        (_, v2, v3), (d1, d2, d3), r = m.vocab_factors, m.dim_factors, m.rank
        i3 = idx % v3
        i2 = (idx // v3) % v2
        i1 = idx // (v2 * v3)
        n1 = _distinct(_per_table(i1, m.vocab_factors[0]))
        n2 = _distinct(_per_table(i2, v2))
        n3 = _distinct(_per_table(i3, v3))
        row_bytes = (n1 * d1 * r + n2 * r * d2 * r + n3 * r * d3) * F32
        # Rebuilding a row is two chained contractions.  Ids that share
        # (i1, i2) can share the first when it is G1.G2, and ids that share
        # (i2, i3) the first when it is G2.G3: take the cheaper order.
        n12 = _distinct(_per_table(idx // v3, m.vocab_factors[0] * v2))
        n23 = _distinct(_per_table(idx % (v2 * v3), v2 * v3))
        nid = _distinct(ids)
        left = n12 * 2 * d1 * r * d2 * r + nid * 2 * d1 * d2 * r * d3
        right = n23 * 2 * r * d2 * r * d3 + nid * 2 * d1 * r * d2 * d3
        flops = min(left, right) + pool_flops
    else:
        raise ValueError(f"no counts for embedding kind {m.kind!r}")
    return {"flops": float(flops), "bytes": float(row_bytes + io_bytes),
            "pooled_bytes": float(b * t * m.dim * F32)}


def head_counts(batch: int, m) -> dict:
    """FLOPs and HBM bytes of the interaction and both MLPs for ``batch``.

    FLOPs: every matmul of both MLPs and the F*(F-1)/2 dot products of the
    interaction.  Bytes: the weights read once, the dense features and the
    pooled embeddings read, one logit per sample written.
    """
    dims = m.mlp_dims()
    mats = [io for part in ("bottom", "top") for io in dims[part]]
    f = m.num_features
    flops = batch * (sum(2 * i * o for i, o in mats) + f * (f - 1) // 2 * 2 * m.dim)
    weights = sum(i * o + o for i, o in mats) * F32
    pooled = batch * m.num_tables * m.dim * F32
    io = batch * m.num_dense * F32 + batch * F32
    return {"flops": float(flops), "bytes": float(weights + pooled + io),
            "pooled_bytes": float(pooled)}


def step_counts(gather: dict, head: dict) -> dict:
    """The whole batch (embedding layer and head) as one step, from its
    ``gather_counts`` and ``head_counts``: the pooled embeddings need never
    leave the chip, so they are not counted."""
    return {"flops": gather["flops"] + head["flops"],
            "bytes": gather["bytes"] - gather["pooled_bytes"]
            + head["bytes"] - head["pooled_bytes"]}
