"""Cached pooled gather-and-reduce — the bg-PIM SRAM cache in a Pallas kernel.

The ProactivePIM cache serves high-intra-GnR-locality rows from bank-group
SRAM while the remaining rows stream from DRAM.  TPU realization:

* the **cache block** — a ``(slots, dim)`` slice holding the rows the prefetch
  scheduler staged for this batch — is mapped into VMEM once (constant
  BlockSpec index map, resident across all grid steps);
* the **slot map** rides in SMEM via scalar prefetch alongside the indices:
  for each bag element the kernel reads ``slot[b, k]`` and routes the access
  — ``slot >= 0`` selects the VMEM cache row, ``slot < 0`` the table row in
  HBM;
* **32-bit tables** run a block of bags per grid step
  (``blocks.run_bag_blocks``): each miss is one row copy the kernel starts
  itself, all of a block's copies in flight at once, and a hit copies
  nothing from HBM — the kernel-level analogue of the cache absorbing DRAM
  accesses;
* **16-bit tables** (bf16 training) stream one row per grid step
  (``blocks.run_bags``), since Mosaic refuses a one-row copy into a packed
  16-bit VMEM buffer: the streamed operand's index map sends misses to
  ``idx[b, k]`` and pins hits to block 0, and Pallas elides the DMA when
  consecutive grid steps name the same block;
* accumulation is fp32 (bank-group MAC + register file), exactly like
  ``gnr_bag``.

Block layout: see ``repro.kernels.blocks``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.blocks import (
    accumulate, f32_rows, resident, row_spec, row_view, run_bag_blocks,
    run_bags, step,
)

DEFAULT_DIM_BLOCK = 512


def bag_blocks(dtype) -> bool:
    """Whether tables of ``dtype`` run ``run_bag_blocks`` (32-bit rows) or
    stream a row per step through ``run_bags`` (narrower rows)."""
    return jnp.dtype(dtype).itemsize == 4


def _cached_row(pos, slot_ref, row_ref, cache_ref):
    s = slot_ref[pos]
    cached = cache_ref[pl.ds(jnp.maximum(s, 0), 1), :]
    return jnp.where(s >= 0, cached, row_ref[...].astype(jnp.float32))


def _cached_kernel(idx_ref, slot_ref, row_ref, cache_ref, out_ref, *, k_steps):
    accumulate(out_ref, _cached_row(step(k_steps), slot_ref, row_ref, cache_ref))


def _cached_qr_kernel(q_idx_ref, slot_ref, r_idx_ref, row_ref, cache_ref,
                      r_lut_ref, out_ref, *, k_steps):
    pos = step(k_steps)
    row = _cached_row(pos, slot_ref, row_ref, cache_ref)
    accumulate(out_ref, row + r_lut_ref[pl.ds(r_idx_ref[pos], 1), :])


def _stream_spec(bd: int, k_steps: int) -> pl.BlockSpec:
    # Misses DMA row idx[b,k]; hits pin the stream to row 0 so consecutive
    # hits revisit the same block and Pallas skips the fetch.
    def row(b, k, idx, slot, *_):
        pos = b * k_steps + k
        return jnp.where(slot[pos] >= 0, 0, idx[pos])

    return row_spec(bd, row)


@functools.partial(jax.jit, static_argnames=("dim_block", "interpret", "name"))
def cached_bag(
    table: jax.Array,
    cache: jax.Array,
    idx: jax.Array,
    slot: jax.Array,
    *,
    dim_block: int | None = None,
    interpret: bool = False,
    name: str = "cached_bag",
) -> jax.Array:
    """Cached pooled bag: out[b] = Σ_k (slot[b,k] >= 0 ? C[slot] : T[idx]).

    table: (rows, dim) or its ``row_view`` in HBM; cache: (slots, dim)
    VMEM-resident (the staged block); idx/slot: (B, K) int32.  Returns
    (B, dim) in the table dtype (fp32 accumulation inside).  ``name`` names
    the kernel in the compiled program.
    """
    k_steps = idx.shape[1]
    dim = table.shape[-1]
    bd = dim_block or min(dim, DEFAULT_DIM_BLOCK)
    assert dim % bd == 0, f"dim {dim} not divisible by dim_block {bd}"
    if bag_blocks(table.dtype):
        out = run_bag_blocks([idx, slot], table, cache, dim=dim, bd=bd,
                             interpret=interpret, name=name)
        return out.astype(table.dtype)
    cache = f32_rows(cache, dim)
    out = run_bags(
        functools.partial(_cached_kernel, k_steps=k_steps),
        [idx, slot], [row_view(table), cache],
        [_stream_spec(bd, k_steps), resident(cache.shape[0], bd)],
        dim=dim, bd=bd, interpret=interpret, name=name,
    )
    return out.astype(table.dtype)


@functools.partial(jax.jit, static_argnames=("dim_block", "interpret", "name"))
def cached_qr_bag(
    q_table: jax.Array,
    cache: jax.Array,
    r_lut: jax.Array,
    q_idx: jax.Array,
    slot: jax.Array,
    r_idx: jax.Array,
    *,
    dim_block: int | None = None,
    interpret: bool = False,
    name: str = "cached_qr_bag",
) -> jax.Array:
    """Cached pooled QR bag:
    out[b] = Σ_k ( (slot >= 0 ? C[slot] : Q[q_idx]) + R[r_idx] ).

    The R LUT and the cache block are both VMEM-resident; only cache misses
    touch HBM.  q_table: (rows, dim) or its ``row_view``; q_idx/slot/r_idx:
    (B, K) int32 -> (B, dim).  ``name`` as in ``cached_bag``.
    """
    k_steps = q_idx.shape[1]
    dim = q_table.shape[-1]
    bd = dim_block or min(dim, DEFAULT_DIM_BLOCK)
    assert dim % bd == 0, f"dim {dim} not divisible by dim_block {bd}"
    assert cache.shape[-1] == dim and r_lut.shape[-1] == dim
    if bag_blocks(q_table.dtype):
        out = run_bag_blocks([q_idx, slot, r_idx], q_table, cache, [r_lut],
                             dim=dim, bd=bd, interpret=interpret, name=name)
        return out.astype(q_table.dtype)
    cache, r_lut = f32_rows(cache, dim), f32_rows(r_lut, dim)
    out = run_bags(
        functools.partial(_cached_qr_kernel, k_steps=k_steps),
        [q_idx, slot, r_idx], [row_view(q_table), cache, r_lut],
        [
            _stream_spec(bd, k_steps),
            resident(cache.shape[0], bd),
            resident(r_lut.shape[0], bd),
        ],
        dim=dim, bd=bd, interpret=interpret, name=name,
    )
    return out.astype(q_table.dtype)
