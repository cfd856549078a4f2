"""Fused quotient–remainder gather kernel (the paper's LUT mechanism on TPU).

One logical lookup = one HBM row DMA (the Q row) + one VMEM LUT read (the R
row).  The naive QR implementation costs two HBM gathers per lookup; pinning
the small shared table in VMEM removes the second one — this kernel *is* the
"shared-table-in-PIM-SRAM" idea expressed in the TPU memory hierarchy:

* ``r_lut``   — whole R table mapped into VMEM once (BlockSpec index_map is
  constant), persisting across all grid steps: the SRAM LUT;
* ``q_table`` — stays in HBM; each grid step DMAs exactly the row named by the
  scalar-prefetched index (``PrefetchScalarGridSpec``), so the *indices run
  ahead of the data* and Pallas double-buffers row ``i+1``'s DMA behind row
  ``i``'s add: the proactive-prefetch analogue;
* the reconstruction add runs on the VPU between DMAs — GnR "in memory".

Grid: one step per lookup row, a second grid dim tiles wide embedding dims so
the VMEM working set stays bounded and lanes stay 128-aligned on hardware.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.blocks import f32_rows, resident, row_spec, row_view, run_bags

# Lane-dim tile for wide embeddings; must divide dim or equal dim.
DEFAULT_DIM_BLOCK = 512


def _kernel(q_idx_ref, r_idx_ref, q_row_ref, r_lut_ref, out_ref):
    r_row = r_lut_ref[pl.ds(r_idx_ref[pl.program_id(0)], 1), :]
    out_ref[...] = q_row_ref[...].astype(jnp.float32) + r_row


@functools.partial(
    jax.jit, static_argnames=("dim_block", "interpret")
)
def qr_gather(
    q_table: jax.Array,
    r_lut: jax.Array,
    q_idx: jax.Array,
    r_idx: jax.Array,
    *,
    dim_block: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """out[n, :] = q_table[q_idx[n], :] + r_lut[r_idx[n], :].

    q_table: (Q, D) float (or its ``row_view``); r_lut: (C, D) same dtype;
    q_idx/r_idx: (N,) int32.  Each lookup is a bag of one (grid (N, 1,
    dim tiles)): one Q row DMA'd from HBM plus one VMEM LUT row.
    """
    dim = q_table.shape[-1]
    bd = dim_block or min(dim, DEFAULT_DIM_BLOCK)
    assert dim % bd == 0, f"dim {dim} not divisible by dim_block {bd}"
    r_lut = f32_rows(r_lut, dim)
    out = run_bags(
        _kernel,
        [q_idx[:, None], r_idx[:, None]],   # q_idx, r_idx ride in SMEM ahead of DMAs
        [row_view(q_table), r_lut],
        [
            # One Q row per step, DMA'd from HBM by prefetched index.
            row_spec(bd, lambda i, k, qi, ri: qi[i]),
            # The LUT: same block every step -> stays resident in VMEM.
            resident(r_lut.shape[0], bd),
        ],
        dim=dim, bd=bd, interpret=interpret,
    )
    return out.astype(q_table.dtype)
