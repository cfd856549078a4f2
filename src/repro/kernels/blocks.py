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
* **Staged rows** (``stage_rows``, the prefetch cache block): an XLA
  gather of rows of a ``row_view`` buffer first copies the whole buffer
  into another tiling, so the kernel copies each row itself.
* **Bag blocks** (``run_bag_blocks``, the dense and QR row gathers): one
  grid step serves a block of ``G`` bags.  The table stays in HBM
  (``memory_space=pl.ANY``) and the step copies each missed row itself, all
  of the block's copies in flight at once, into a ``(K, G, bd)`` VMEM
  buffer; the K-sum is then K adds of sublane-dense ``(G, bd)`` slabs into
  a ``(G, bd)`` output block.  ``G`` comes from the shapes alone
  (``block_grid``).
* **TT bag blocks** (``run_tt_blocks``, the packed TT megakernel on 32-bit
  rows): one grid step serves a block of ``G`` bags the same way, but an
  entry stages a whole ``(r, d2*r)`` middle-core row (one copy a miss, a
  cache-row copy a hit) and its two outer-core rows, and the block's ``G*K``
  accesses are then contracted together (``tt_gather.contract_block``).
  ``G`` comes from the shapes alone (``tt_block_grid``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Scalar-prefetch budget for one chunk's index streams (of the 1 MiB SMEM).
SMEM_STREAM_BYTES = 512 * 2**10

# VMEM for a bag block's two row buffers, beside the resident blocks'
# budget (``packed_gather.VMEM_RESIDENT_BUDGET``, 12 MiB): together they
# stay under the 16 MiB a kernel may use by default.
BLOCK_SCRATCH_BYTES = 2 * 2**20
VMEM_DEFAULT_LIMIT = 16 * 2**20

# VMEM for a TT bag block's two buffers of middle-core rows: at the
# published widths (K 32, 8 KiB rows) 8 bags a step.
TT_BLOCK_SCRATCH_BYTES = 4 * 2**20


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


def bag_grid(bags: int, k_steps: int, n_streams: int, dim: int,
             bd: int) -> tuple[int, int, int]:
    """``(chunk, n_chunks, steps)`` of ``run_bags`` over ``bags`` bags of
    ``k_steps`` entries in ``n_streams`` index streams: each chunk is one
    grid ``(chunk, k_steps, dim // bd)`` sized so its streams fit
    ``SMEM_STREAM_BYTES``; ``steps`` counts every grid step of every chunk,
    pad bags included."""
    per_bag = 4 * k_steps * n_streams
    chunk = min(bags, max(8, SMEM_STREAM_BYTES // per_bag // 8 * 8))
    n = -(-bags // chunk)
    return chunk, n, n * chunk * k_steps * (dim // bd)


def run_bags(body, streams, operands, in_specs, *, dim: int, bd: int,
             interpret: bool, name: str | None = None) -> jax.Array:
    """Run a bag kernel over every bag: grid ``(bags, K, dim // bd)``.

    ``streams`` are (B, K) int index streams, scalar-prefetched flat (a body
    or index map reads bag b's k-th entry at ``b * K + k``, see ``step``);
    ``body(*stream_refs, *operand_refs, out_ref)`` accumulates bag b into
    its ``(1, bd)`` fp32 output row.  The bag axis is chunked to fit the
    streams in SMEM (``bag_grid``); pad bags read entry 0 of every stream
    and are dropped.  ``name`` names the ``pallas_call`` (the kernel's name
    in the compiled program).  Returns (B, dim) f32.
    """
    bsz, k_steps = streams[0].shape
    chunk, n, _steps = bag_grid(bsz, k_steps, len(streams), dim, bd)
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
        name=name,
    )
    return _over_chunks(call, streams, operands, chunk, n, dim)


def _over_chunks(call, streams, operands, chunk: int, n: int,
                 dim: int) -> jax.Array:
    """Run ``call`` once per chunk of ``chunk`` bags (``n`` of them, under a
    ``lax.map`` when more than one), its (B, K) ``streams`` padded and passed
    flat, then ``operands``; the pad bags' rows are dropped."""
    bsz, k_steps = streams[0].shape
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


def _blocking(bags: int, k_steps: int, n_streams: int,
              bd: int) -> tuple[int, int, int]:
    """``(g, chunk, n_chunks)`` of ``run_bag_blocks``: ``g`` bags a step, the
    largest multiple of 8 whose two ``(K, g, bd)`` f32 row buffers fit
    ``BLOCK_SCRATCH_BYTES`` (fewer for a short stream); chunks as in
    ``bag_grid``, rounded down to whole blocks."""
    g = max(8, BLOCK_SCRATCH_BYTES // (2 * k_steps * bd * 4) // 8 * 8)
    g = min(g, -(-bags // 8) * 8)
    fit = SMEM_STREAM_BYTES // (4 * k_steps * n_streams) // g * g
    chunk = min(-(-bags // g) * g, max(g, fit))
    return g, chunk, -(-bags // chunk)


def block_grid(bags: int, k_steps: int, n_streams: int, dim: int,
               bd: int) -> tuple[int, int, int]:
    """``(chunk, n_chunks, steps)`` of ``run_bag_blocks`` over ``bags`` bags
    of ``k_steps`` entries in ``n_streams`` index streams: one grid step per
    block of bags and lane tile, pad bags included."""
    g, chunk, n = _blocking(bags, k_steps, n_streams, bd)
    return chunk, n, n * (chunk // g) * (dim // bd)


def _bag_block_kernel(*refs, n_luts, g, k_steps, bd):
    # refs: the idx, slot and LUT streams (SMEM); the table (HBM), cache and
    # LUTs (VMEM); the (g, bd) output block; scratch: two (K, g, bd) row
    # buffers, their per-bag LUT sums, a DMA semaphore and a miss count each.
    idx_ref, slot_ref = refs[:2]
    lut_idx = refs[2:2 + n_luts]
    table_ref, cache_ref = refs[2 + n_luts:4 + n_luts]
    luts = refs[4 + n_luts:4 + 2 * n_luts]
    out_ref, rows, lut_sum, sems, misses = refs[4 + 2 * n_luts:]
    j, b = pl.program_id(0), pl.program_id(1)
    lanes = pl.ds(pl.multiple_of(j * bd, bd), bd)

    def copy(row, buf, k, gi):
        return pltpu.make_async_copy(
            table_ref.at[row, :, lanes], rows.at[buf, k, pl.ds(gi, 1)],
            sems.at[buf])

    def stage(blk, buf):
        # Start block ``blk``'s miss copies into buffer ``buf``; copy its
        # hits from the cache and sum its LUT rows, all of them in VMEM.
        base = blk * (g * k_steps)

        def bag(gi, n_miss):
            def entry(k, carry):
                acc, n = carry
                pos = base + gi * k_steps + k
                s = slot_ref[pos]

                @pl.when(s < 0)
                def _miss():
                    copy(idx_ref[pos], buf, k, gi).start()

                @pl.when(s >= 0)
                def _hit():
                    rows[buf, k, pl.ds(gi, 1)] = cache_ref[pl.ds(s, 1), :]

                for li, lut in zip(lut_idx, luts):
                    acc = acc + lut[pl.ds(li[pos], 1), :]
                return acc, n + (s < 0).astype(jnp.int32)

            # Unrolled over the bag: the step's price is scalar work per
            # entry, and the unrolled loop took 28% less of it on a v5e.
            acc, n_miss = jax.lax.fori_loop(
                0, k_steps, entry, (jnp.zeros((1, bd), jnp.float32), n_miss),
                unroll=True)
            lut_sum[buf, pl.ds(gi, 1)] = acc
            return n_miss

        misses[buf] = jax.lax.fori_loop(0, g, bag, jnp.int32(0))

    # Two buffers: block b+1's copies fly while block b is summed.  The
    # chain restarts at each lane tile, whose cache and LUT tiles differ.
    cur = b % 2

    @pl.when(b == 0)
    def _first():
        stage(b, cur)

    @pl.when(b + 1 < pl.num_programs(1))
    def _next():
        stage(b + 1, 1 - cur)

    def wait(_, c):
        copy(0, cur, 0, 0).wait()
        return c

    jax.lax.fori_loop(0, misses[cur], wait, 0)
    out_ref[...] = jax.lax.fori_loop(
        0, k_steps, lambda k, acc: acc + rows[cur, k], lut_sum[cur])


def run_bag_blocks(streams, table, cache, luts=(), *, dim: int, bd: int,
                   interpret: bool, name: str | None = None) -> jax.Array:
    """Pooled row gather, a block of bags per grid step:
    ``out[b] = sum_k (slot >= 0 ? cache[slot] : table[idx]) + lut_i[li]``.

    ``streams`` are the (B, K) int streams ``idx``, ``slot`` and one per
    LUT, scalar-prefetched flat and chunked to fit SMEM (``block_grid``);
    pad bags read entry 0 of every stream and are dropped.  ``table`` (rows,
    dim) f32 or its ``row_view`` stays in HBM, and each miss (``slot < 0``)
    is one row copy; a hit reads the resident ``cache`` (slots, dim) and
    copies nothing from HBM; each resident LUT (rows, dim) adds its row per
    entry.  Grid ``(dim // bd, blocks)``, sums in f32.  ``name`` names the
    ``pallas_call``.  Returns (B, dim) f32.
    """
    bsz, k_steps = streams[0].shape
    assert table.dtype.itemsize == 4, table.dtype
    g, chunk, n = _blocking(bsz, k_steps, len(streams), bd)
    cache = f32_rows(cache, dim)
    luts = [f32_rows(t, dim) for t in luts]

    def tile(rows):
        return pl.BlockSpec((rows, bd), lambda j, b, *_: (0, j),
                            pipeline_mode=pl.Buffered(1))

    # resident tiles, both row buffers and LUT sums, the output's two
    # blocks, and 1 MiB for Mosaic's own
    vmem = 4 * bd * (sum(a.shape[0] for a in (cache, *luts))
                     + 2 * g * k_steps + 4 * g) + 2**20
    call = pl.pallas_call(
        functools.partial(_bag_block_kernel, n_luts=len(luts), g=g,
                          k_steps=k_steps, bd=bd),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(streams),
            grid=(dim // bd, chunk // g),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY), tile(cache.shape[0]),
                      *(tile(t.shape[0]) for t in luts)],
            out_specs=pl.BlockSpec((g, bd), lambda j, b, *_: (b, j)),
            scratch_shapes=[
                pltpu.VMEM((2, k_steps, g, bd), jnp.float32),
                pltpu.VMEM((2, g, bd), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((2,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((chunk, dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem if vmem > VMEM_DEFAULT_LIMIT else None),
        interpret=interpret,
        name=name,
    )
    return _over_chunks(call, streams, (row_view(table), cache, *luts),
                        chunk, n, dim)


def _tt_blocking(bags: int, k_steps: int, n_streams: int,
                 row_bytes: int) -> tuple[int, int, int]:
    """``(g, chunk, n_chunks)`` of ``run_tt_blocks``: ``g`` bags a step, the
    largest multiple of 8 whose two ``(K, g)`` buffers of ``row_bytes``
    middle-core rows fit ``TT_BLOCK_SCRATCH_BYTES`` (fewer for a short
    stream); chunks as in ``bag_grid``, rounded down to whole blocks."""
    g = max(8, TT_BLOCK_SCRATCH_BYTES // (2 * k_steps * row_bytes) // 8 * 8)
    g = min(g, -(-bags // 8) * 8)
    fit = SMEM_STREAM_BYTES // (4 * k_steps * n_streams) // g * g
    chunk = min(-(-bags // g) * g, max(g, fit))
    return g, chunk, -(-bags // chunk)


def tt_block_grid(bags: int, k_steps: int, n_streams: int,
                  row_bytes: int) -> tuple[int, int, int]:
    """``(chunk, n_chunks, steps)`` of ``run_tt_blocks`` over ``bags`` bags
    of ``k_steps`` entries in ``n_streams`` index streams, middle-core rows
    of ``row_bytes``: one grid step per block of bags, pad bags included."""
    g, chunk, n = _tt_blocking(bags, k_steps, n_streams, row_bytes)
    return chunk, n, n * (chunk // g)


def _tt_block_kernel(i1_ref, i2_ref, i3_ref, slot_ref, g2_ref, cache_ref,
                     g1_ref, g3_ref, out_ref, rows, outer1, outer3, chat,
                     sems, misses, *, g, k_steps, contract):
    # Scratch: two buffers each of the block's middle-core rows (access
    # n = k * g + bag at rows n*r .. n*r + r), its G1 and G3 rows (row n),
    # the contraction's scratch, a DMA semaphore and a miss count each.
    b = pl.program_id(0)
    rank = cache_ref.shape[1]

    def at(n):
        return pl.ds(pl.multiple_of(n * rank, rank), rank)

    def copy(row, buf, n):
        return pltpu.make_async_copy(g2_ref.at[row], rows.at[buf, at(n)],
                                     sems.at[buf])

    def stage(blk, buf):
        # Start block ``blk``'s miss copies into buffer ``buf``; copy its
        # hits from the cache and its outer-core rows, all of them in VMEM.
        base = blk * (g * k_steps)

        def bag(gi, n_miss):
            def entry(k, n_miss):
                pos = base + gi * k_steps + k
                n = k * g + gi
                s = slot_ref[pos]

                @pl.when(s < 0)
                def _miss():
                    copy(i2_ref[pos], buf, n).start()

                @pl.when(s >= 0)
                def _hit():
                    rows[buf, at(n)] = cache_ref[s]

                outer1[buf, pl.ds(n, 1)] = g1_ref[pl.ds(i1_ref[pos], 1), :]
                outer3[buf, pl.ds(n, 1)] = g3_ref[pl.ds(i3_ref[pos], 1), :]
                return n_miss + (s < 0).astype(jnp.int32)

            return jax.lax.fori_loop(0, k_steps, entry, n_miss, unroll=True)

        misses[buf] = jax.lax.fori_loop(0, g, bag, jnp.int32(0))

    # Two buffers: block b+1's copies fly while block b is contracted.
    cur = b % 2

    @pl.when(b == 0)
    def _first():
        stage(b, cur)

    @pl.when(b + 1 < pl.num_programs(0))
    def _next():
        stage(b + 1, 1 - cur)

    def wait(_, c):
        copy(0, cur, 0).wait()
        return c

    jax.lax.fori_loop(0, misses[cur], wait, 0)
    out_ref[...] = contract(rows.at[cur], outer1.at[cur], outer3.at[cur], chat,
                           g=g)


def run_tt_blocks(streams, g2, cache, g1, g3, *, contract, dim: int,
                  chat_width: int, interpret: bool,
                  name: str | None = None) -> jax.Array:
    """Pooled TT gather, a block of bags per grid step:
    ``out[b] = contract over k of G1[i1] · (slot >= 0 ? C[slot] : G2[i2]) ·
    G3[i3]``.

    ``streams`` are the (B, K) int streams ``i1``, ``i2``, ``i3`` and
    ``slot``, scalar-prefetched flat and chunked to fit SMEM
    (``tt_block_grid``); pad bags read entry 0 of every stream and are
    dropped.  ``g2`` (rows, r, d2*r) f32 stays in HBM, and each miss is one
    row copy; a hit copies its row of the resident ``cache`` (slots, r,
    d2*r); the outer cores ``g1`` / ``g3`` (rows, ·) f32 are resident too.
    ``contract(rows, a, c, chat, g=g)`` turns a block's staged rows (refs:
    the (g*K*r, d2*r) middle-core rows, the (g*K, ·) outer-core rows and a
    (g*K, ``chat_width``) scratch) into its (g, ``dim``) pooled rows.  Grid
    ``(blocks,)``.  ``name`` names the ``pallas_call``.  Returns (B, dim)
    f32.
    """
    bsz, k_steps = streams[0].shape
    rank, width = g2.shape[1], g2.shape[1] * g2.shape[2]
    assert g2.dtype.itemsize == 4, g2.dtype
    g, chunk, n = _tt_blocking(bsz, k_steps, len(streams), 4 * width)
    acc = g * k_steps
    # The outer cores padded to whole lane tiles: Mosaic slices a buffer of
    # their rows only at whole tiles.
    g1, g3 = (jnp.pad(a, ((0, 0), (0, -a.shape[1] % 128))) for a in (g1, g3))

    def whole(a):
        return pl.BlockSpec(a.shape, lambda b, *_: (0,) * a.ndim,
                            pipeline_mode=pl.Buffered(1))

    # resident blocks, both row buffers and outer-core row buffers, the
    # contraction scratch, the output's two blocks, and 4 MiB for Mosaic's
    # own (the contraction's temporaries)
    vmem = 4 * (cache.size + g1.size + g3.size
                + acc * (2 * width + 2 * g1.shape[1] + 2 * g3.shape[1]
                         + chat_width) + 2 * g * dim) + 4 * 2**20
    call = pl.pallas_call(
        functools.partial(_tt_block_kernel, g=g, k_steps=k_steps,
                          contract=contract),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(streams),
            grid=(chunk // g,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY), whole(cache),
                      whole(g1), whole(g3)],
            out_specs=pl.BlockSpec((g, dim), lambda b, *_: (b, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, acc * rank, width // rank), jnp.float32),
                pltpu.VMEM((2, acc, g1.shape[1]), jnp.float32),
                pltpu.VMEM((2, acc, g3.shape[1]), jnp.float32),
                pltpu.VMEM((acc, chat_width), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((2,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((chunk, dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem if vmem > VMEM_DEFAULT_LIMIT else None),
        interpret=interpret,
        name=name,
    )
    return _over_chunks(call, streams, (g2, cache, g1, g3), chunk, n, dim)


STAGE_ROWS_A_STEP = 512


def _stage_kernel(rows_ref, table_ref, out_ref, sem, *, g):
    base = pl.program_id(0) * g

    def start(i, c):
        pltpu.make_async_copy(table_ref.at[rows_ref[base + i]],
                              out_ref.at[pl.ds(i, 1)], sem).start()
        return c

    def wait(i, c):
        pltpu.make_async_copy(table_ref.at[0], out_ref.at[pl.ds(0, 1)],
                              sem).wait()
        return c

    jax.lax.fori_loop(0, g, start, 0)
    jax.lax.fori_loop(0, g, wait, 0)


def stage_rows(table, rows, *, interpret: bool,
               name: str = "cache_stage") -> jax.Array:
    """``table[rows]`` as (n, dim) for a 32-bit ``(rows, 1, dim)`` buffer
    left in HBM: each row one copy into the output block, all of a step's
    ``STAGE_ROWS_A_STEP`` copies in flight at once.  ``rows`` are
    scalar-prefetched (SMEM), so a few tens of thousands at most."""
    table = row_view(table)
    n, dim = rows.shape[0], table.shape[-1]
    g = min(STAGE_ROWS_A_STEP, -(-n // 8) * 8)
    steps = -(-n // g)
    rows = jnp.pad(rows.astype(jnp.int32), (0, steps * g - n))
    out = pl.pallas_call(
        functools.partial(_stage_kernel, g=g),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(steps,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((g, dim), lambda b, *_: (b, 0)),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=jax.ShapeDtypeStruct((steps * g, dim), table.dtype),
        interpret=interpret,
        name=name,
    )(rows, table)
    return out[:n]

