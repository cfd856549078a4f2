"""Block layouts and the bag grid the TPU compiler accepts for the row-gather
kernels (dense / QR / TT bags, cached or not).

* **Row blocks.**  Mosaic requires a block's last two dims to be
  (8, 128)-divisible or to span the array, so a one-row ``(1, bd)`` block of
  a 2-D ``(rows, dim)`` table is refused.  The row kernels therefore stream
  from — and accumulate into — a 3-D ``(rows, 1, dim)`` view with a
  ``(None, 1, bd)`` block: the unit middle dim spans its axis.  The view
  costs no HBM padding (``compiled.memory_analysis()`` on a described v5e,
  812,501 x 128 table: 396.7 MiB in f32 and 198.4 MiB in bf16, 2-D or
  3-D); it is free when a
  buffer is built in that shape (``repro.core.packed_tables.pack_params``)
  and one copy otherwise.
* **Resident blocks** (prefetch cache, R LUT, TT outer cores) stay 2-D and
  are read one row at a dynamic sublane offset, which Mosaic supports for
  32-bit data only, so they are held in f32.  Their index map is constant
  over the bag and K axes, so they are single-buffered.
* **Index streams** ride in SMEM as scalar-prefetch operands, which the
  chip caps at 1 MiB in all.  A 2-D ``(B, K)`` int32 stream is padded there
  to 128 lanes, so the streams are passed flat (bag ``b``'s k-th entry at
  ``b * K + k``) and the bag axis runs in chunks whose streams fit
  ``SMEM_STREAM_BYTES`` — one ``pallas_call`` per chunk under a
  ``lax.map``.  A serving batch (2048 samples x 26 tables = 53,248 bags of
  32) is about a hundred chunks.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Scalar-prefetch budget for one chunk's index streams (of the 1 MiB SMEM).
SMEM_STREAM_BYTES = 512 * 2**10


def row_view(table: jax.Array) -> jax.Array:
    """``(rows, dim)`` -> the ``(rows, 1, dim)`` view the row kernels stream
    from (identity on a buffer already in that shape)."""
    return table.reshape(table.shape[0], 1, table.shape[-1])


def f32_rows(x: jax.Array, dim: int) -> jax.Array:
    """A resident block as 2-D f32 (dynamic-row reads need 32-bit data)."""
    return x.reshape(x.shape[0], dim).astype(jnp.float32)


def resident(rows: int, bd: int) -> pl.BlockSpec:
    """A VMEM-resident 2-D block: constant over (b, k), tiled by lane block
    ``j``, single-buffered (its index never changes within a lane tile)."""
    return pl.BlockSpec(
        (rows, bd), lambda b, k, j, *_: (0, j), pipeline_mode=pl.Buffered(1)
    )


def row_spec(bd: int, index) -> pl.BlockSpec:
    """One ``(1, bd)`` row of a ``row_view`` buffer; ``index(b, k, *streams)``
    names the row."""
    return pl.BlockSpec(
        (None, 1, bd), lambda b, k, j, *sc: (index(b, k, *sc), 0, j)
    )


def step(k_steps: int):
    """Flat stream position of the current (bag, k) grid step."""
    return pl.program_id(0) * k_steps + pl.program_id(1)


def accumulate(out_ref, row) -> None:
    """Bag-sum into the fp32 output row, revisited across the K steps."""
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = row

    @pl.when(k > 0)
    def _acc():
        out_ref[...] = out_ref[...] + row


def run_bags(body, streams, operands, in_specs, *, dim: int, bd: int,
             interpret: bool) -> jax.Array:
    """Run a bag kernel over every bag: grid ``(bags, K, dim // bd)``.

    ``streams`` are (B, K) int index streams, scalar-prefetched flat (a body
    or index map reads bag b's k-th entry at ``b * K + k``, see ``step``);
    ``body(*stream_refs, *operand_refs, out_ref)`` accumulates bag b into
    its ``(1, bd)`` fp32 output row.  The bag axis is chunked to fit the
    streams in SMEM; pad bags read entry 0 of every stream and are dropped.
    Returns (B, dim) f32.
    """
    bsz, k_steps = streams[0].shape
    per_bag = 4 * k_steps * len(streams)
    chunk = min(bsz, max(8, SMEM_STREAM_BYTES // per_bag // 8 * 8))
    n = -(-bsz // chunk)
    call = pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(streams),
            grid=(chunk, k_steps, dim // bd),
            in_specs=in_specs,
            out_specs=row_spec(bd, lambda b, k, *_: b),
        ),
        out_shape=jax.ShapeDtypeStruct((chunk, 1, dim), jnp.float32),
        interpret=interpret,
    )
    flat = [
        jnp.pad(s.astype(jnp.int32), ((0, n * chunk - bsz), (0, 0)))
        .reshape(n, chunk * k_steps)
        for s in streams
    ]
    if n == 1:
        out = call(*(f[0] for f in flat), *operands)
    else:
        out = jax.lax.map(lambda fs: call(*fs, *operands), flat)
    return out.reshape(n * chunk, dim)[:bsz]
