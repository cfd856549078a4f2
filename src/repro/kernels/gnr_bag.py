"""Pooled gather-and-reduce kernel (embedding bag) with fused QR LUT.

Computes ``out[b] = Σ_k ( Q[q_idx[b,k]] + R[r_idx[b,k]] )`` — the DLRM bag
operator with the weight-sharing reconstruction folded into the reduction.

TPU realization of the PIM partial-GnR unit:

* grid ``(B, K, dim_tiles)`` — the output block for bag ``b`` is *revisited*
  across the K steps (TPU grids execute sequentially, so in-place accumulation
  into the output block is the idiomatic reduction pattern);
* the accumulator lives in VMEM in fp32 (MAC-unit accuracy), initialized at
  k==0 and written through on every step — bank-group MAC + register file;
* Q rows stream from HBM via scalar-prefetched index maps (double-buffered by
  the Pallas pipeline = proactive prefetch), R rows come from the resident
  VMEM LUT; one bag element costs one HBM row, not two.

A dense (non-weight-sharing) variant is included for baseline benches.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.blocks import (
    accumulate, f32_rows, resident, row_spec, row_view, run_bags, step,
)

DEFAULT_DIM_BLOCK = 512


def _qr_kernel(q_idx_ref, r_idx_ref, q_row_ref, r_lut_ref, out_ref, *, k_steps):
    # out_ref is the fp32 VMEM accumulator (bank-group MAC register file);
    # it is revisited across the K grid steps of the same bag.
    r_row = r_lut_ref[pl.ds(r_idx_ref[step(k_steps)], 1), :]
    accumulate(out_ref, q_row_ref[...].astype(jnp.float32) + r_row)


def _dense_kernel(idx_ref, row_ref, out_ref):
    accumulate(out_ref, row_ref[...].astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("dim_block", "interpret"))
def gnr_bag(
    q_table: jax.Array,
    r_lut: jax.Array,
    q_idx: jax.Array,
    r_idx: jax.Array,
    *,
    dim_block: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Pooled QR bag. q_idx/r_idx: (B, K) int32 -> out (B, D)."""
    k_steps = q_idx.shape[1]
    dim = q_table.shape[-1]
    bd = dim_block or min(dim, DEFAULT_DIM_BLOCK)
    assert dim % bd == 0, f"dim {dim} not divisible by dim_block {bd}"
    r_lut = f32_rows(r_lut, dim)
    out = run_bags(
        functools.partial(_qr_kernel, k_steps=k_steps),
        [q_idx, r_idx], [row_view(q_table), r_lut],
        [
            row_spec(bd, lambda b, k, qi, ri: qi[b * k_steps + k]),
            resident(r_lut.shape[0], bd),
        ],
        dim=dim, bd=bd, interpret=interpret,
    )
    return out.astype(q_table.dtype)


@functools.partial(jax.jit, static_argnames=("dim_block", "interpret"))
def gnr_bag_dense(
    table: jax.Array,
    idx: jax.Array,
    *,
    dim_block: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Pooled dense bag (baseline: no weight sharing). idx: (B, K) -> (B, D)."""
    k_steps = idx.shape[1]
    dim = table.shape[-1]
    bd = dim_block or min(dim, DEFAULT_DIM_BLOCK)
    assert dim % bd == 0
    out = run_bags(
        _dense_kernel, [idx], [row_view(table)],
        [row_spec(bd, lambda b, k, i: i[b * k_steps + k])],
        dim=dim, bd=bd, interpret=interpret,
    )
    return out.astype(table.dtype)
