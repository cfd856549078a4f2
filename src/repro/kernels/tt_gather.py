"""Fused TT-Rec gather-contract bag kernel (the paper's TT path on TPU).

One pooled TT lookup = one HBM row DMA (the middle core G2) + two VMEM reads
(the outer cores) + two tiny matmuls.  The naive TT implementation gathers
three cores from main memory per lookup and ships partial contractions over
the CPU-PIM link; this kernel is the paper's TT execution expressed in the
TPU memory hierarchy:

* ``g1`` / ``g3`` — whole outer cores mapped into VMEM once (constant
  BlockSpec index maps, resident across all grid steps): the bg-PIM SRAM
  cache holding the high-intra-GnR-locality subtables;
* ``g2``          — stays in HBM; each grid step DMAs exactly the row named by
  the scalar-prefetched ``i2`` (``PrefetchScalarGridSpec``), so indices run
  ahead of data and Pallas double-buffers step ``k+1``'s DMA behind step
  ``k``'s contraction — the proactive-prefetch analogue;
* the chained contraction ``(d1,r)@(r,d2*r)`` then ``(d1*d2,r)@(r,d3)`` runs
  between DMAs, and the per-bag sum accumulates in an fp32 VMEM block that is
  revisited across the K grid steps (bank-group MAC + register file) — the
  subtable-duplication move that removes the CPU-side combine.

Grid ``(B, K, 1)``: one step per bag element (the unit axis keeps the
index-map convention of ``repro.kernels.blocks``).  The embedding dim is NOT
tiled — the contraction needs the whole G2 row, and TT dims are small by
construction (``dim <= 1024`` for every recommendation config here), so one
output block per bag stays far under the VMEM budget.

Layout for the TPU compiler: Mosaic cannot split a vector's lane axis, so the
flat-row reshapes ``(1, d1*r) -> (d1, r)`` etc. are refused.  The middle core
is streamed already in its matmul shape — ``g2_view``, ``(rows, r, d2*r)``,
one (r, d2*r) block per row, (8, 128)-aligned at r = 16 — and ``contract``
unflattens the small outer-core rows and flattens the result with 0/1
selection matmuls built from iotas (exact at HIGHEST precision).

``contract_block`` is the same contraction for a block of bags at once (the
packed megakernel's block path, ``blocks.run_tt_blocks``): no per-access
matmul, the outer-core products on the VPU, and the 0/1 matmuls applied once
a block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.blocks import accumulate, f32_rows, resident, run_bags, step

_HIGHEST = jax.lax.Precision.HIGHEST


def g2_view(g2: jax.Array, rank: int) -> jax.Array:
    """``(rows, r*d2*r)`` -> ``(rows, r, d2*r)``: each middle-core row in its
    matmul shape (identity on a buffer already in that shape)."""
    return g2.reshape(g2.shape[0], rank, -1)


def _sel(shape: tuple[int, int], pred) -> jax.Array:
    """0/1 f32 matrix with ``pred(row, col)`` true."""
    i = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    j = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return pred(i, j).astype(jnp.float32)


def _dot(x, y):
    return jnp.dot(x, y, preferred_element_type=jnp.float32, precision=_HIGHEST)


def contract(a_row, m, c_row, *, d1: int, d2: int, d3: int, rank: int):
    """G1[i1] · G2[i2] · G3[i3] -> the ``(1, d1*d2*d3)`` embedding row.

    ``a_row`` (1, d1*r) and ``c_row`` (1, r*d3) are flat outer-core rows;
    ``m`` (r, d2*r) is the middle-core row in matmul shape.  Every step is a
    2-D matmul or mask; the unflattening and the final flatten are 0/1
    selection matmuls instead of the lane-splitting reshapes Mosaic refuses.
    """
    r, d23 = rank, d2 * d3
    # A[a, p] = a_row[a*r + p]
    a = _dot(a_row * _sel((d1, d1 * r), lambda i, j: j // r == i),
             _sel((d1 * r, r), lambda i, j: i % r == j))
    t = _dot(a, m)                                   # (d1, d2*r): [a, (b, q)]
    # kron(I_d2, C) with C[q, c] = c_row[q*d3 + c]: (d2*r, d2*d3)
    e = c_row * _sel((d2 * r, r * d3), lambda i, j: i % r == j // d3)
    cbig = _dot(e, _sel((r * d3, d23), lambda i, j: i % d3 == j % d3))
    cbig = cbig * _sel((d2 * r, d23), lambda i, j: i // r == j // d3)
    u = _dot(t, cbig)                                # (d1, d2*d3): [a, (b, c)]
    # row-major flatten of u into one lane-dense row
    v = _dot(u, _sel((d23, d1 * d23), lambda i, j: j % d23 == i))
    v = v * _sel((d1, d1 * d23), lambda i, j: j // d23 == i)
    return jnp.sum(v, axis=0, keepdims=True)


def contract_block(rows, a_ref, c_ref, chat_ref, *, g: int, d1: int, d2: int,
                   d3: int, rank: int):
    """The pooled rows of a block of ``g`` bags, every access contracted
    together: ``(g, d1*d2*d3)`` f32.

    Refs: ``rows`` (N*r, d2*r) holds the block's N = K*g middle-core rows,
    access ``n = k*g + bag`` at rows ``n*r .. n*r + r``; ``a_ref`` (N, d1*r)
    and ``c_ref`` (N, r*d3) its flat outer-core rows (lanes past those
    ignored); ``chat_ref`` (N, d3*d2*r) is scratch.  With ``M_p`` the
    (N, d2*r) slab of sublane p of every middle row (a strided load) and
    ``A``, ``C`` the outer rows:

    * ``T_a = sum_p A[:, a*r+p] * M_p`` on the VPU, lanes ``(b, q)``;
    * ``Chat_c[:, b*r+q] = C[:, q*d3+c]``, one 0/1 matmul for the block;
    * ``T_a * Chat_c`` summed over each bag's K entries into (g, d2*r);
    * the sum over q and the flatten to lane ``a*d2*d3 + b*d3 + c``, one 0/1
      matmul and a lane roll per (a, c), on those g rows alone.

    Every matmul's right side is a fixed 0/1 matrix, at HIGHEST (exact);
    the rest is f32 arithmetic on the VPU.
    """
    r, w, dim = rank, d2 * rank, d1 * d2 * d3
    n_acc = a_ref.shape[0]
    chat_ref[...] = _dot(c_ref[...], _sel(
        (c_ref.shape[1], d3 * w),
        lambda i, j: (i % d3 == j // w) & (i // d3 == j % r)))

    def pool(k, acc):
        base = k * g
        a = a_ref[pl.ds(base, g), :]
        ch = chat_ref[pl.ds(base, g), :]
        m = [rows[pl.ds(base * r + p, g, stride=r), :] for p in range(r)]
        acc = list(acc)
        for ai in range(d1):
            t = a[:, ai * r:ai * r + 1] * m[0]
            for p in range(1, r):
                t = t + a[:, ai * r + p:ai * r + p + 1] * m[p]
            for ci in range(d3):
                acc[ai * d3 + ci] += t * ch[:, ci * w:(ci + 1) * w]
        return tuple(acc)

    acc = jax.lax.fori_loop(0, n_acc // g, pool,
                            (jnp.zeros((g, w), jnp.float32),) * (d1 * d3))
    # sum over q into lane b*d3, then roll to a*d2*d3 + b*d3 + c
    u = _dot(jnp.concatenate(acc, axis=0),
             _sel((w, dim), lambda i, j: j == i // r * d3))
    out = u[:g]
    for i in range(1, d1 * d3):
        ai, ci = divmod(i, d3)
        out = out + pltpu.roll(u[i * g:(i + 1) * g], ai * d2 * d3 + ci, 1)
    return out


def _kernel(
    i1_ref, i2_ref, i3_ref,      # scalar-prefetched flat (B*K,) index maps
    g2_row_ref,                  # (r, d2*r) — the streamed middle-core row
    g1_ref,                      # (v1, d1*r)  — VMEM-resident outer core (f32)
    g3_ref,                      # (v3, r*d3)  — VMEM-resident outer core (f32)
    out_ref,                     # (1, d1*d2*d3) fp32 accumulator
    *,
    d1: int, d2: int, d3: int, rank: int, k_steps: int,
):
    pos = step(k_steps)
    row = contract(
        g1_ref[pl.ds(i1_ref[pos], 1), :],
        g2_row_ref[...].astype(jnp.float32),
        g3_ref[pl.ds(i3_ref[pos], 1), :],
        d1=d1, d2=d2, d3=d3, rank=rank,
    )
    accumulate(out_ref, row)


def g2_spec(rank: int, width: int, index) -> pl.BlockSpec:
    """One middle-core row of a ``g2_view`` buffer, named by
    ``index(b, k, *streams)``."""
    return pl.BlockSpec(
        (None, rank, width // rank), lambda b, k, j, *sc: (index(b, k, *sc), 0, 0)
    )


@functools.partial(jax.jit, static_argnames=("dims", "interpret"))
def tt_bag(
    g1: jax.Array,
    g2: jax.Array,
    g3: jax.Array,
    i1: jax.Array,
    i2: jax.Array,
    i3: jax.Array,
    *,
    dims: tuple[int, int, int, int],
    interpret: bool = False,
) -> jax.Array:
    """Pooled TT bag: out[b] = Σ_k G1[i1[b,k]] · G2[i2[b,k]] · G3[i3[b,k]].

    g1: (v1, d1*r); g2: (v2, r*d2*r) or its ``g2_view``; g3: (v3, r*d3) —
    same dtype; i1/i2/i3: (B, K) int32.  ``dims`` = (d1, d2, d3, rank),
    static.  Returns (B, d1*d2*d3) in the table dtype (fp32 inside).
    """
    d1, d2, d3, rank = dims
    k_steps = i1.shape[1]
    dim = d1 * d2 * d3
    width = rank * d2 * rank
    assert g1.shape[1] == d1 * rank, (g1.shape, dims)
    assert g2.size == g2.shape[0] * width, (g2.shape, dims)
    assert g3.shape[1] == rank * d3, (g3.shape, dims)
    g1, g3 = f32_rows(g1, g1.shape[1]), f32_rows(g3, g3.shape[1])
    out = run_bags(
        functools.partial(
            _kernel, d1=d1, d2=d2, d3=d3, rank=rank, k_steps=k_steps
        ),
        [i1, i2, i3],               # i1, i2, i3 ride in SMEM ahead of the DMAs
        [g2_view(g2, rank), g1, g3],
        [
            # One middle-core row per step, DMA'd from HBM by prefetched i2.
            g2_spec(rank, width, lambda b, k, i1, i2, i3: i2[b * k_steps + k]),
            # Outer cores: same block every step -> stay resident in VMEM.
            resident(*g1.shape),
            resident(*g3.shape),
        ],
        dim=dim, bd=dim, interpret=interpret,
    )
    return out.astype(g2.dtype)
