"""Packed-table execution layout — one buffer, one index stream, one kernel.

The DLRM embedding layer is T independent tables with identical row width.
Launching one gather kernel per table costs T dispatches and T short
HBM-streaming loops per batch; the paper's bg-PIM (like RecNMP/TensorDIMM)
wins by batching many small gathers into one wide memory-side pass.  This
module builds that pass for the TPU:

* ``PackedLayout`` — a static (hashable, jit-friendly) description of all
  same-width subtables concatenated row-major: per-table row offsets for the
  big subtables (dense table / QR Q / TT middle core G2), for the small
  shared subtables (QR R LUTs, TT outer cores G1/G3), and for the per-table
  cache-slot ranges of the prefetch scheduler;
* ``pack_params`` — the device-side concatenation (+ one trailing all-zero
  row per streamed buffer: accesses that must contribute nothing — ragged
  bag tails, non-owned rows on a shard — are *routed to the zero row*
  instead of masked, so the kernel needs no predication).  The streamed
  buffer is built directly in the megakernel's row layout (``stream_view``),
  so a serving session pays for that layout once, at pack time;
* ``pack_indices`` — logical (B, T, K) bag indices -> globally-offset int32
  streams, vectorized over all tables at once (the per-table Python loop
  becomes index arithmetic);
* slot-map helpers translating each table's local prefetch-scheduler state
  into the packed cache block's coordinates;
* ``packed_multi_bag_lookup`` — the drop-in multi-table GnR used by the
  single-chip model forward: pack, stream, one
  ``ops.packed_multi_pooled`` dispatch (megakernel on TPU, packed jnp oracle
  elsewhere; differentiable on both paths).

The sharded two-level path builds its own local streams (ownership / hot-tier
/ position routing) in ``repro.core.sharded_embedding`` but lands in the same
megakernel.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hashing, qr_embedding
from repro.core.embedding_bag import BagConfig


def _cumsum(sizes: Sequence[int]) -> tuple[int, ...]:
    off, acc = [], 0
    for s in sizes:
        off.append(acc)
        acc += int(s)
    return tuple(off)


@dataclasses.dataclass(frozen=True)
class PackedLayout:
    """Static shape/offset description of one packed multi-table family.

    All tables must share ``kind`` and row width (the DLRM convention); the
    per-table row counts may differ.  Hashable — safe as a jit static arg.
    """

    kind: str                                   # dense | qr | tt
    num_tables: int
    dim: int                                    # pooled output width
    rows_per_table: tuple[int, ...]             # big-subtable physical rows
    small_rows_per_table: tuple[int, ...] = ()  # QR R rows (empty otherwise)
    slot_budgets: tuple[int, ...] = ()          # cache slots per table
    collision: int = 0                          # QR hash collision value
    tt_dims: tuple[int, int, int, int] | None = None    # (d1, d2, d3, rank)
    tt_vocab: tuple[int, int, int] | None = None        # (v1, v2, v3)

    # -- big (streamed) buffer ------------------------------------------------
    @property
    def row_offsets(self) -> tuple[int, ...]:
        return _cumsum(self.rows_per_table)

    @property
    def total_rows(self) -> int:
        return sum(self.rows_per_table)

    @property
    def zero_row(self) -> int:
        """Index of the appended all-zero row (ragged/masked accesses)."""
        return self.total_rows

    @property
    def big_width(self) -> int:
        """Row width of the streamed buffer (G2 is wider than dim for TT)."""
        if self.kind == "tt":
            d1, d2, d3, rank = self.tt_dims
            return rank * d2 * rank
        return self.dim

    # -- small shared buffer (QR R LUTs) -------------------------------------
    @property
    def small_offsets(self) -> tuple[int, ...]:
        return _cumsum(self.small_rows_per_table)

    @property
    def total_small(self) -> int:
        return sum(self.small_rows_per_table)

    @property
    def small_zero_row(self) -> int:
        return self.total_small

    # -- packed cache block ---------------------------------------------------
    @property
    def slot_offsets(self) -> tuple[int, ...]:
        return _cumsum(self.slot_budgets)

    @property
    def total_slots(self) -> int:
        return sum(self.slot_budgets)


# ---------------------------------------------------------------------------
# layout construction
# ---------------------------------------------------------------------------

def packable(bags: Sequence[BagConfig]) -> bool:
    """True when every bag can ride one packed megakernel dispatch: uniform
    kind (dense / additive-QR / TT), row width, vocab, and decomposition
    constants across tables (the DLRM convention).  Uniform vocab keeps the
    per-table hot-slot maps stackable on the sharded path; mixed-vocab sets
    fall back to the per-table loop."""
    if not bags:
        return False
    e0 = bags[0].emb
    if e0.kind not in ("dense", "qr", "tt"):
        return False
    if e0.kind == "qr" and e0.reconstruction != "add":
        return False
    for b in bags:
        e = b.emb
        if e.kind != e0.kind or e.dim != e0.dim or e.vocab != e0.vocab:
            return False
        if e.kind == "qr" and e.collision != e0.collision:
            return False
        if e.kind == "tt" and (
            e.tt_spec.vocab_factors != e0.tt_spec.vocab_factors
            or e.tt_spec.dim_factors != e0.tt_spec.dim_factors
            or e.tt_spec.rank != e0.tt_spec.rank
        ):
            return False
    return True


def build_layout(
    bags: Sequence[BagConfig], slot_budgets: Sequence[int] | None = None
) -> PackedLayout:
    assert packable(bags), "bags are not uniform enough to pack"
    e0 = bags[0].emb
    budgets = tuple(int(s) for s in (slot_budgets or [0] * len(bags)))
    assert len(budgets) == len(bags)
    if e0.kind == "qr":
        return PackedLayout(
            kind="qr",
            num_tables=len(bags),
            dim=e0.dim,
            rows_per_table=tuple(
                qr_embedding._pad_rows(b.emb.qr_spec.q_rows) for b in bags
            ),
            small_rows_per_table=tuple(b.emb.qr_spec.r_rows for b in bags),
            slot_budgets=budgets,
            collision=e0.collision,
        )
    if e0.kind == "tt":
        spec = e0.tt_spec
        return PackedLayout(
            kind="tt",
            num_tables=len(bags),
            dim=e0.dim,
            rows_per_table=tuple(b.emb.tt_spec.g2_rows_padded for b in bags),
            slot_budgets=budgets,
            tt_dims=(spec.d1, spec.d2, spec.d3, spec.rank),
            tt_vocab=spec.vocab_factors,
        )
    return PackedLayout(
        kind="dense",
        num_tables=len(bags),
        dim=e0.dim,
        rows_per_table=tuple(qr_embedding._pad_rows(b.emb.vocab) for b in bags),
        slot_budgets=budgets,
    )


def shard_layout(layout: PackedLayout, chips: int) -> PackedLayout:
    """The layout of one chip's share of row-sharded tables: each big
    subtable's rows split evenly over ``chips`` (the padded row counts divide
    by any chip count that divides ``ROW_PAD``), the small subtables as they
    are, and each table's slot budget (the chip's cache block) at most the
    chip's rows of it, as ``PrefetchScheduler`` caps it."""
    if any(r % chips for r in layout.rows_per_table):
        raise ValueError(f"{chips} chips do not divide the tables' rows "
                         f"{layout.rows_per_table}")
    rows = tuple(r // chips for r in layout.rows_per_table)
    return dataclasses.replace(
        layout, rows_per_table=rows,
        slot_budgets=tuple(min(b, r) for b, r in zip(layout.slot_budgets, rows)))


@functools.lru_cache(maxsize=64)
def _layout_for(bags: tuple) -> PackedLayout:
    return build_layout(list(bags))


def layout_for(bags: Sequence[BagConfig]) -> PackedLayout:
    """Cached layout lookup (BagConfig is frozen/hashable)."""
    return _layout_for(tuple(bags))


# ---------------------------------------------------------------------------
# device-side packing
# ---------------------------------------------------------------------------

def big_key(kind: str) -> str:
    """Param-dict key of the streamed big subtable for an embedding kind."""
    return {"qr": "q", "tt": "g2"}.get(kind, "table")


def combiner_scale(bags: Sequence[BagConfig], dtype) -> jax.Array:
    """(T,) per-table post-pool scale implementing the bag combiners."""
    return jnp.asarray(
        [1.0 / b.pooling if b.combiner == "mean" else 1.0 for b in bags], dtype
    )


def concat_with_zero(parts: Sequence[jax.Array], dtype) -> jax.Array:
    """Row-concatenate buffers and append one all-zero row (the routing sink
    for accesses that must contribute nothing)."""
    zero = jnp.zeros((1, *parts[0].shape[1:]), dtype)
    return jnp.concatenate([p.astype(dtype) for p in parts] + [zero], axis=0)


def stream_view(big: jax.Array, layout: PackedLayout) -> jax.Array:
    """A streamed buffer in the megakernel's row layout: ``(rows, 1, dim)``
    for dense / QR (``kernels.blocks.row_view``), ``(rows, r, d2*r)`` for the
    TT middle core (``kernels.tt_gather.g2_view``).  Same row-major bytes as
    the flat ``(rows, width)`` buffer; the oracles take either."""
    from repro.kernels import blocks, tt_gather

    if layout.kind == "tt":
        return tt_gather.g2_view(big, layout.tt_dims[3])
    return blocks.row_view(big)


def pack_params(tables: Sequence[dict], layout: PackedLayout, *, dtype=None) -> dict:
    """Concatenate per-table params into the packed buffers (+ zero rows).

    Streamed buffers (big table, QR R LUT) get one trailing all-zero row so
    masked accesses can be routed instead of predicated.  Outer TT cores are
    packed without a zero row — a zero G2 row already nulls the contraction.
    The big buffer is concatenated straight into its ``stream_view``.
    """
    key = big_key(layout.kind)
    dtype = dtype or tables[0][key].dtype
    big = concat_with_zero(
        [stream_view(t[key], layout) for t in tables], dtype
    )
    assert big.shape[0] == layout.total_rows + 1, (big.shape, layout.rows_per_table)
    if layout.kind == "qr":
        r = concat_with_zero([t["r"] for t in tables], dtype)
        assert r.shape[0] == layout.total_small + 1
        return {"q": big, "r": r}
    if layout.kind == "tt":
        g1 = jnp.concatenate([t["g1"].astype(dtype) for t in tables], axis=0)
        g3 = jnp.concatenate([t["g3"].astype(dtype) for t in tables], axis=0)
        return {"g1": g1, "g2": big, "g3": g3}
    return {"table": big}


# ---------------------------------------------------------------------------
# index-stream packing (vectorized over all tables)
# ---------------------------------------------------------------------------

def _valid_mask(idx: jax.Array, lengths: jax.Array | None) -> jax.Array | None:
    if lengths is None:
        return None
    k = idx.shape[-1]
    return jnp.arange(k, dtype=jnp.int32)[None, None, :] < lengths[..., None]


def pack_indices(
    idx: jax.Array, layout: PackedLayout, *, lengths: jax.Array | None = None,
    keep: jax.Array | None = None,
) -> dict:
    """Logical (B, T, K) bag indices -> globally-offset packed streams.

    ``lengths`` (B, T) optionally marks ragged bags: positions ``k >=
    lengths[b, t]`` are routed to the zero rows and contribute nothing —
    empty bags (length 0) pool to exactly zero.  ``keep`` (B, T, K), where
    given, routes the entries it marks False to the zero rows as well.
    """
    idx = idx.astype(jnp.int32)
    assert idx.shape[-2] == layout.num_tables, (idx.shape, layout.num_tables)
    off = jnp.asarray(layout.row_offsets, jnp.int32)[None, :, None]
    mask = _valid_mask(idx, lengths)
    if keep is not None:
        mask = keep if mask is None else mask & keep

    if layout.kind == "qr":
        q_idx, r_idx = hashing.qr_decompose(idx, layout.collision)
        q_g = q_idx + off
        r_g = r_idx + jnp.asarray(layout.small_offsets, jnp.int32)[None, :, None]
        if mask is not None:
            q_g = jnp.where(mask, q_g, layout.zero_row)
            r_g = jnp.where(mask, r_g, layout.small_zero_row)
        return {"q_idx": q_g, "r_idx": r_g}
    if layout.kind == "tt":
        from repro.core import tt_embedding

        v1, v2, v3 = layout.tt_vocab
        i1, i2, i3 = tt_embedding.tt_decompose_factors(idx, v2, v3)
        t_ids = jnp.arange(layout.num_tables, dtype=jnp.int32)[None, :, None]
        i1_g = i1 + t_ids * v1
        i3_g = i3 + t_ids * v3
        i2_g = i2 + off
        if mask is not None:
            # zero G2 row nulls the product; i1/i3 stay valid rows
            i2_g = jnp.where(mask, i2_g, layout.zero_row)
        return {"i1": i1_g, "i2": i2_g, "i3": i3_g}
    g = idx + off
    if mask is not None:
        g = jnp.where(mask, g, layout.zero_row)
    return {"idx": g}


def global_slots(slot: jax.Array, layout: PackedLayout) -> jax.Array:
    """Per-table local cache slots (B, T, K), -1 = miss -> packed-block slots."""
    off = jnp.asarray(layout.slot_offsets, jnp.int32)[None, :, None]
    slot = slot.astype(jnp.int32)
    return jnp.where(slot >= 0, slot + off, -1)


def owned_entries(idx: jax.Array, layout: PackedLayout, shard: jax.Array
                  ) -> tuple[jax.Array, jax.Array]:
    """Which logical (B, T, K) entries chip ``shard`` owns under the
    ``shard_layout`` ``layout`` (its big-subtable row lies in the chip's
    range), and the entries as logical ids of the chip's share: an owned
    entry's big row becomes local, its QR remainder stays; others read 0."""
    per = jnp.asarray(layout.rows_per_table, jnp.int32)[None, :, None]
    if layout.kind == "qr":
        per = per * layout.collision         # logical ids of a chip's Q rows
    local = idx.astype(jnp.int32) - shard * per
    owned = (local >= 0) & (local < per)
    return jnp.where(owned, local, 0), owned


def miss_slots(idx: jax.Array) -> jax.Array:
    """All-miss slot map (the no-cache / mesh configuration)."""
    return jnp.full(idx.shape, -1, jnp.int32)


def packed_cache_rows(
    cache_rows: Sequence[np.ndarray], layout: PackedLayout
) -> np.ndarray:
    """Per-table scheduler ``cache_rows()`` -> global packed-buffer rows.

    The packed cache block is ``big[packed_cache_rows(...)]`` — one gather is
    the whole staging DMA for every table's slots.
    """
    parts = []
    for t, rows in enumerate(cache_rows):
        assert rows.shape == (layout.slot_budgets[t],), (
            rows.shape, layout.slot_budgets[t])
        parts.append(np.asarray(rows, np.int64) + layout.row_offsets[t])
    total = (
        np.concatenate(parts) if parts else np.empty((0,), np.int64)
    )
    return total.astype(np.int32)


def shard_cache_rows(
    cache_rows: Sequence[np.ndarray], layout: PackedLayout, chips: int
) -> np.ndarray:
    """Per-table scheduler ``cache_rows()`` of row-sharded tables (each
    ``chips`` blocks of the table's slot budget, of rows the block's chip
    owns) -> (chips, total_slots) rows of each chip's packed share, under
    the ``shard_layout`` ``layout``."""
    parts = []
    for t, rows in enumerate(cache_rows):
        n = layout.slot_budgets[t]
        assert rows.shape == (chips * n,), (rows.shape, chips, n)
        base = np.arange(chips, dtype=np.int64)[:, None] * layout.rows_per_table[t]
        parts.append(rows.reshape(chips, n) - base + layout.row_offsets[t])
    return np.concatenate(parts, axis=1).astype(np.int32)


def dummy_cache(layout: PackedLayout, dtype) -> jax.Array:
    """1-row zero cache block for cache-less calls (slot map all -1)."""
    return jnp.zeros((1, layout.big_width), dtype)


# ---------------------------------------------------------------------------
# single-chip multi-table GnR (the model-forward entry point)
# ---------------------------------------------------------------------------

def packed_multi_bag_lookup(
    tables: Sequence[dict],
    indices: jax.Array,
    bags: Sequence[BagConfig],
    *,
    lengths: jax.Array | None = None,
    exec_mode: str = "auto",
    interpret: bool | None = None,
    dim_block: int | None = None,
) -> jax.Array:
    """All-tables GnR in one megakernel dispatch. ``indices``: (B, T, K).

    Drop-in for ``embedding_bag.multi_bag_lookup`` on packable bag sets: the
    per-table Python loop (T kernel launches / T gathers) becomes one packed
    dispatch.  Returns (B, T, dim) in the compute dtype.
    """
    from repro.kernels import ops

    layout = layout_for(bags)
    emb = bags[0].emb
    packed = pack_params(tables, layout, dtype=emb.compute_dtype)
    streams = pack_indices(indices, layout, lengths=lengths)
    streams["slot"] = miss_slots(indices)
    packed["cache"] = dummy_cache(layout, emb.compute_dtype)
    pooled = ops.packed_multi_pooled(
        packed, streams, kind=layout.kind, dims=layout.tt_dims,
        exec_mode=exec_mode, interpret=interpret, dim_block=dim_block,
    )
    if lengths is None:
        pooled = pooled * combiner_scale(bags, pooled.dtype)[None, :, None]
    else:
        # mean combiners divide by the VALID bag length, not the padded K
        mean_t = jnp.asarray([b.combiner == "mean" for b in bags])
        denom = jnp.where(
            mean_t[None, :], jnp.maximum(lengths, 1).astype(pooled.dtype), 1.0
        )
        pooled = pooled / denom[..., None]
    return pooled.astype(emb.compute_dtype)
