"""Multi-table megakernel: packed-table fused gather-and-reduce.

The serving and mesh paths used to launch one Pallas kernel per embedding
table — a 26-table DLRM paid 26 dispatches plus 26 short HBM-streaming loops
per batch.  ProactivePIM's bg-PIM wins by batching many small gathers into one
wide memory-side pass (the RecNMP / TensorDIMM observation); the TPU analogue
is a single kernel over a **packed** layout:

* all same-width big subtables (dense tables / QR Q tables / TT middle cores)
  are concatenated row-major into ONE buffer; per-table row offsets turn the
  logical (table_id, row) pair into a flat packed row id **before** the kernel
  — the index streams arriving here are already global;
* bags from every table ride one flattened stream: bag ``g`` is
  ``(sample b, table t) = divmod(g, T)``; the kernel never sees table
  boundaries, so HBM row DMAs pipeline *across* tables instead of draining
  per-table loops back-to-back;
* the small shared subtables of every table (QR R LUTs, TT outer cores) are
  packed the same way and mapped into VMEM once — one resident block serves
  all tables;
* cache-slot routing (the prefetch scheduler) is folded in: ``slot >= 0``
  reads the packed VMEM cache block (per-table slot ranges concatenated),
  ``slot < 0`` fetches the HBM row, so hits issue no HBM traffic;
* rows of 32 bits run a block of bags per grid step, the kernel starting
  every missed row's copy itself: dense and QR rows through
  ``blocks.run_bag_blocks``, TT middle-core rows through
  ``blocks.run_tt_blocks``, which contracts the block's accesses together
  (``tt_gather.contract_block``); 16-bit rows stream one row per grid step
  (``blocks.run_bags``), accumulating in fp32 in a VMEM output block
  revisited across the K steps.

The mesh path calls the same kernels with a 1-row dummy cache and an all-miss
slot map: masking (non-owned rows, off-shard R positions, ragged bag tails)
is expressed by routing those accesses to an appended all-zero row, so one
kernel body covers cached serving, sharded partials, and ragged bags.

Layout construction and index-stream packing live in
``repro.core.packed_tables``; pure-jnp oracles in ``ref.py``
(``packed_bag_ref`` / ``packed_qr_bag_ref`` / ``packed_tt_bag_ref``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import cached_gather as _cg
from repro.kernels import tt_gather as _tt
from repro.kernels.blocks import (
    accumulate, f32_rows, resident, run_bags, run_tt_blocks, step,
)

# Budget for the VMEM-RESIDENT operands of one dispatch (packed cache block +
# packed R LUT / TT outer cores; constant index maps keep them live across the
# whole grid).  Layout builders must size slot budgets under this — see
# DLRMConfig.cache_vmem_mb — so the guard failing means a mis-sized layout,
# caught at trace time instead of as a Mosaic VMEM OOM.
VMEM_RESIDENT_BUDGET = 12 * 2**20


def _check_resident(**blocks) -> None:
    total = sum(a.size * a.dtype.itemsize for a in blocks.values())
    assert total <= VMEM_RESIDENT_BUDGET, (
        f"VMEM-resident operands {total / 2**20:.1f} MiB exceed the "
        f"{VMEM_RESIDENT_BUDGET / 2**20:.0f} MiB budget: "
        + ", ".join(f"{k}={tuple(v.shape)}" for k, v in blocks.items())
        + " — shrink the cache slot budget (cache_vmem_mb) or the packed LUTs"
    )


# ---------------------------------------------------------------------------
# kernel bodies
# ---------------------------------------------------------------------------

def _packed_tt_kernel(
    i1_ref, i2_ref, i3_ref, slot_ref,   # scalar-prefetched flat (G*K,) streams
    g2_row_ref,                          # (r, d2*r) streamed middle-core row
    cache_ref,                           # (slots, r, d2*r) staged G2 rows (f32)
    g1_ref,                              # (T*v1, d1*r) packed outer cores (f32)
    g3_ref,                              # (T*v3, r*d3) packed outer cores (f32)
    out_ref,                             # (1, d1*d2*d3) fp32 accumulator
    *,
    d1: int, d2: int, d3: int, rank: int, k_steps: int,
):
    pos = step(k_steps)
    s = slot_ref[pos]
    m = jnp.where(s >= 0, cache_ref[jnp.maximum(s, 0)],
                  g2_row_ref[...].astype(jnp.float32))
    row = _tt.contract(
        g1_ref[pl.ds(i1_ref[pos], 1), :], m, g3_ref[pl.ds(i3_ref[pos], 1), :],
        d1=d1, d2=d2, d3=d3, rank=rank,
    )
    accumulate(out_ref, row)


# ---------------------------------------------------------------------------
# megakernel dispatchers (one pallas_call for ALL tables)
# ---------------------------------------------------------------------------

def packed_bag(
    table: jax.Array,
    cache: jax.Array,
    idx: jax.Array,
    slot: jax.Array,
    *,
    dim_block: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Packed dense megabag: out[g] = Σ_k (slot[g,k] >= 0 ? C[slot] : T[idx]).

    table: (total_rows, dim) or its ``blocks.row_view`` — ALL tables
    concatenated (+ trailing zero row);
    cache: (total_slots, dim) packed staged block; idx/slot: (G, K) int32
    with G = batch * num_tables and idx already globally offset.

    The kernel body IS ``cached_gather.cached_bag``: the multi-table fusion
    lives entirely in the pre-offset index stream and the packed buffers, so
    the slot routing stays single-sourced.  This wrapper
    adds the packed-layout VMEM-residency guard (the cache block here holds
    EVERY table's slots).  Returns (G, dim) in the table dtype.
    """
    _check_resident(cache=cache)
    return _cg.cached_bag(
        table, cache, idx, slot, dim_block=dim_block, interpret=interpret,
        name="packed_dense_bag",
    )


def packed_qr_bag(
    q_table: jax.Array,
    cache: jax.Array,
    r_lut: jax.Array,
    q_idx: jax.Array,
    slot: jax.Array,
    r_idx: jax.Array,
    *,
    dim_block: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Packed QR megabag:
    out[g] = Σ_k ( (slot >= 0 ? C[slot] : Q[q_idx]) + R[r_idx] ).

    q_table: (total_q_rows, dim) or its ``blocks.row_view``, all Q tables
    packed (+ zero row); r_lut:
    (total_r_rows, dim) all R LUTs packed (+ zero row), VMEM-resident as one
    block; q_idx/slot/r_idx: (G, K) globally-offset streams -> (G, dim).
    Kernel body = ``cached_gather.cached_qr_bag`` over the packed buffers
    (see ``packed_bag``), plus the packed-layout residency guard.
    """
    _check_resident(cache=cache, r_lut=r_lut)
    return _cg.cached_qr_bag(
        q_table, cache, r_lut, q_idx, slot, r_idx,
        dim_block=dim_block, interpret=interpret, name="packed_qr_bag",
    )


@functools.partial(jax.jit, static_argnames=("dims", "interpret"))
def packed_tt_bag(
    g1: jax.Array,
    g2: jax.Array,
    g3: jax.Array,
    cache: jax.Array,
    i1: jax.Array,
    i2: jax.Array,
    i3: jax.Array,
    slot: jax.Array,
    *,
    dims: tuple[int, int, int, int],
    interpret: bool = False,
) -> jax.Array:
    """Packed TT megabag with slot-routed middle core:
    out[g] = Σ_k G1[i1] · (slot >= 0 ? C[slot] : G2[i2]) · G3[i3].

    g1: (T*v1, d1*r) / g3: (T*v3, r*d3) — every table's outer cores packed and
    VMEM-resident (the bg-PIM SRAM pin, now shared by the whole model);
    g2: (total_v2_rows, r*d2*r) packed middle cores (+ zero row), flat or in
    ``tt_gather.g2_view``; cache: the staged G2 rows, same width.
    i1/i2/i3/slot: (G, K) globally offset.  ``dims`` = (d1, d2, d3, rank),
    static.  32-bit rows run a block of bags per grid step
    (``blocks.run_tt_blocks``), 16-bit rows one access per step
    (``blocks.run_bags``).  Returns (G, d1*d2*d3).
    """
    d1, d2, d3, rank = dims
    k_steps = i1.shape[1]
    dim = d1 * d2 * d3
    width = rank * d2 * rank
    assert g1.shape[1] == d1 * rank, (g1.shape, dims)
    assert g2.size == g2.shape[0] * width, (g2.shape, dims)
    assert g3.shape[1] == rank * d3, (g3.shape, dims)
    assert cache.size == cache.shape[0] * width, (cache.shape, g2.shape)
    _check_resident(cache=cache, g1=g1, g3=g3)
    cache = _tt.g2_view(cache, rank).astype(jnp.float32)
    g1, g3 = f32_rows(g1, g1.shape[1]), f32_rows(g3, g3.shape[1])
    if _cg.bag_blocks(g2.dtype):
        out = run_tt_blocks(
            [i1, i2, i3, slot], _tt.g2_view(g2, rank), cache, g1, g3,
            contract=functools.partial(
                _tt.contract_block, d1=d1, d2=d2, d3=d3, rank=rank),
            dim=dim, chat_width=d3 * d2 * rank, interpret=interpret,
            name="packed_tt_bag",
        )
        return out.astype(g2.dtype)

    def g2_row(g, k, i1, i2, i3, sl):
        # Streamed G2 row: misses DMA i2's packed row, hits pin row 0.
        pos = g * k_steps + k
        return jnp.where(sl[pos] >= 0, 0, i2[pos])

    out = run_bags(
        functools.partial(
            _packed_tt_kernel, d1=d1, d2=d2, d3=d3, rank=rank, k_steps=k_steps
        ),
        [i1, i2, i3, slot],
        [_tt.g2_view(g2, rank), cache, g1, g3],
        [
            _tt.g2_spec(rank, width, g2_row),
            pl.BlockSpec(
                cache.shape, lambda g, k, j, *_: (0, 0, 0),
                pipeline_mode=pl.Buffered(1),
            ),
            resident(*g1.shape),
            resident(*g3.shape),
        ],
        dim=dim, bd=dim, interpret=interpret, name="packed_tt_bag",
    )
    return out.astype(g2.dtype)
