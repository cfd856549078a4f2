"""Two-level sharded gather-and-reduce — the paper's PIM scheme on a TPU mesh.

Mapping (see DESIGN.md §2):

* bank-group PIM  -> one chip holding a contiguous **row shard** of the Q/dense
  table in HBM; it gathers + partially reduces only rows it owns ("local GnR");
* base-die PIM    -> a single ``psum`` over the `model` mesh axis combining the
  per-shard pooled partials (one vector per bag — never raw rows on the wire);
* SRAM LUT        -> the R table **replicated** on every chip; R contributions
  are served locally and spread across shards by bag position for load balance;
* HBM hot tier    -> the hottest Q rows replicated on every chip (TierPlan);
  on TPU the win is Zipf load-balance: skewed rows no longer hammer one
  shard's HBM, and no extra collective is introduced (hot partials ride the
  same psum).

Associativity of the ``add`` reconstruction is what legalizes all of this —
exactly the paper's argument for why Q rows and R rows may live anywhere.

All ``*_partial`` functions run **inside** ``shard_map`` and take local shards.
They are the kernel-level pieces the engine (``repro.engine``) composes —
every jitted GnR path is built through ``repro.engine``'s
plan/compile/execute API (the deprecated ``build_*`` / ``cached_bag_lookup``
shims were removed after their two-PR grace window; see CHANGES.md).
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import hashing
from repro.core.embedding_bag import BagConfig
from repro.core.qr_embedding import EmbeddingConfig

# Q tables are padded so every potential model-axis size divides the row count.
ROW_PAD = 128


def padded_q_rows(cfg: EmbeddingConfig) -> int:
    """Padded rows of the row-sharded ("big") table: Q for the QR path, the
    middle core G2 for the TT path, the whole table otherwise."""
    if cfg.kind == "qr":
        rows = cfg.qr_spec.q_rows
    elif cfg.kind == "tt":
        rows = cfg.tt_spec.v2
    else:
        rows = cfg.vocab
    return -(-rows // ROW_PAD) * ROW_PAD


def pad_q_table(table: jax.Array, cfg: EmbeddingConfig) -> jax.Array:
    rows = padded_q_rows(cfg)
    if table.shape[0] == rows:
        return table
    pad = rows - table.shape[0]
    return jnp.pad(table, ((0, pad), (0, 0)))


@dataclasses.dataclass(frozen=True)
class ShardPlan:
    """Static description of one table's tiered sharding."""

    cfg: EmbeddingConfig
    num_shards: int                      # size of the row-shard ("model") axis
    num_hot: int = 0                     # replicated-tier rows (0 = no hot tier)

    @property
    def q_rows_padded(self) -> int:
        return padded_q_rows(self.cfg)

    @property
    def rows_per_shard(self) -> int:
        return self.q_rows_padded // self.num_shards


# ---------------------------------------------------------------------------
# local ("bank-group") partials — run inside shard_map
# ---------------------------------------------------------------------------

def _owned_rows_gather(
    q_shard: jax.Array, q_idx: jax.Array, plan: ShardPlan, axis: str
) -> jax.Array:
    """Gather rows of ``q_idx`` owned by this shard; zeros elsewhere.

    q_shard: (rows_per_shard, dim) local. q_idx: (...,) global Q-row ids.
    """
    shard = jax.lax.axis_index(axis)
    local = q_idx - shard * plan.rows_per_shard
    owned = (local >= 0) & (local < plan.rows_per_shard)
    local = jnp.clip(local, 0, plan.rows_per_shard - 1)
    rows = q_shard[local]
    return rows * owned[..., None].astype(rows.dtype)


def qr_bag_partial(
    q_shard: jax.Array,
    r_full: jax.Array,
    idx: jax.Array,
    plan: ShardPlan,
    *,
    axis: str = "model",
    hot_table: jax.Array | None = None,
    hot_slot: jax.Array | None = None,
    weights: jax.Array | None = None,
) -> jax.Array:
    """Local pooled partial for one QR-add bag. idx: (..., pooling) -> (..., dim).

    Tier routing per index:
      hot   -> replicated table, spread across shards by bag position;
      cold  -> owner shard's local Q shard;
      R     -> replicated LUT, spread by bag position.
    The caller psums the result over ``axis`` (the base-die combine).
    """
    cfg = plan.cfg
    shard = jax.lax.axis_index(axis)
    nsh = plan.num_shards
    q_idx, r_idx = hashing.qr_decompose(idx, cfg.collision)
    pooling = idx.shape[-1]
    # Spread replicated-tier work across shards by position (paper: R tables
    # spread evenly across LUTs / load balance between bank groups).
    pos_mine = (jnp.arange(pooling, dtype=jnp.int32) % nsh) == shard

    compute = cfg.compute_dtype
    if hot_table is not None:
        slot = hot_slot[q_idx]                       # (..., pooling)
        is_hot = slot >= 0
        hot_rows = hot_table.astype(compute)[jnp.clip(slot, 0)]
        hot_rows = hot_rows * (is_hot & pos_mine)[..., None].astype(compute)
        cold_gather_idx = q_idx
        cold_rows = _owned_rows_gather(q_shard.astype(compute), cold_gather_idx, plan, axis)
        cold_rows = cold_rows * (~is_hot)[..., None].astype(compute)
        q_rows = hot_rows + cold_rows
    else:
        q_rows = _owned_rows_gather(q_shard.astype(compute), q_idx, plan, axis)

    r_rows = r_full.astype(compute)[r_idx] * pos_mine[..., None].astype(compute)
    rows = q_rows + r_rows
    if weights is not None:
        rows = rows * weights[..., None].astype(compute)
    return rows.sum(axis=-2)


def tt_bag_partial(
    g1_full: jax.Array,
    g2_shard: jax.Array,
    g3_full: jax.Array,
    idx: jax.Array,
    plan: ShardPlan,
    *,
    axis: str = "model",
    hot_table: jax.Array | None = None,
    hot_slot: jax.Array | None = None,
    weights: jax.Array | None = None,
) -> jax.Array:
    """Local pooled partial for one TT bag. idx: (..., pooling) -> (..., dim).

    Tier routing mirrors the QR path, applied to the *middle core*:
      hot  -> replicated hot-G2 rows, spread across shards by bag position;
      cold -> owner shard's local G2 row shard;
      G1/G3 -> duplicated whole on every shard (the bg-PIM SRAM pin), so the
               full chained contraction runs where the G2 row lives and only
               the pooled vector crosses the network (one psum by the caller).
    Correctness rests on the contraction being *linear in G2*: zeroed
    non-owned rows contribute exactly zero to the psum — the TT analogue of
    the QR add-associativity argument.
    """
    from repro.core import tt_embedding

    cfg = plan.cfg
    spec = cfg.tt_spec
    shard = jax.lax.axis_index(axis)
    nsh = plan.num_shards
    i1, i2, i3 = tt_embedding.tt_decompose(idx, spec)
    pooling = idx.shape[-1]
    pos_mine = (jnp.arange(pooling, dtype=jnp.int32) % nsh) == shard

    compute = cfg.compute_dtype
    if hot_table is not None:
        slot = hot_slot[i2]                          # (..., pooling)
        is_hot = slot >= 0
        hot_rows = hot_table.astype(compute)[jnp.clip(slot, 0)]
        hot_rows = hot_rows * (is_hot & pos_mine)[..., None].astype(compute)
        cold_rows = _owned_rows_gather(g2_shard.astype(compute), i2, plan, axis)
        cold_rows = cold_rows * (~is_hot)[..., None].astype(compute)
        g2_rows = hot_rows + cold_rows
    else:
        g2_rows = _owned_rows_gather(g2_shard.astype(compute), i2, plan, axis)

    rows = tt_embedding.contract_rows(
        g1_full.astype(compute)[i1], g2_rows, g3_full.astype(compute)[i3], spec
    )
    if weights is not None:
        rows = rows * weights[..., None].astype(compute)
    return rows.sum(axis=-2)


def dense_bag_partial(
    table_shard: jax.Array,
    idx: jax.Array,
    plan: ShardPlan,
    *,
    axis: str = "model",
    weights: jax.Array | None = None,
) -> jax.Array:
    """Local pooled partial for a dense (non-weight-sharing) bag."""
    rows = _owned_rows_gather(table_shard.astype(plan.cfg.compute_dtype), idx, plan, axis)
    if weights is not None:
        rows = rows * weights[..., None].astype(rows.dtype)
    return rows.sum(axis=-2)


def qr_token_partial(
    q_shard: jax.Array,
    r_full: jax.Array,
    idx: jax.Array,
    plan: ShardPlan,
    *,
    axis: str = "model",
    hot_table: jax.Array | None = None,
    hot_slot: jax.Array | None = None,
) -> jax.Array:
    """Per-token (no pooling) partial: idx (...,) -> (..., dim); psum over axis.

    R rows are replicated so only shard 0 contributes them (no position axis to
    spread over); hot rows likewise. The psum exists only for cold Q rows —
    with a hot tier covering all requests it degenerates to a local lookup.
    """
    cfg = plan.cfg
    shard = jax.lax.axis_index(axis)
    q_idx, r_idx = hashing.qr_decompose(idx, cfg.collision)
    compute = cfg.compute_dtype
    first = (shard == 0)

    if hot_table is not None:
        slot = hot_slot[q_idx]
        is_hot = slot >= 0
        hot_rows = hot_table.astype(compute)[jnp.clip(slot, 0)]
        hot_rows = hot_rows * (is_hot & first)[..., None].astype(compute)
        cold = _owned_rows_gather(q_shard.astype(compute), q_idx, plan, axis)
        cold = cold * (~is_hot)[..., None].astype(compute)
        q_rows = hot_rows + cold
    else:
        q_rows = _owned_rows_gather(q_shard.astype(compute), q_idx, plan, axis)

    r_rows = r_full.astype(compute)[r_idx] * jnp.asarray(first, compute)
    return q_rows + r_rows


# ---------------------------------------------------------------------------
# packed-table local GnR (the multi-table megakernel inside shard_map)
# ---------------------------------------------------------------------------

def packed_local_partial(
    tables: Sequence[dict],
    indices: jax.Array,
    bags: Sequence[BagConfig],
    plans: Sequence[ShardPlan],
    *,
    axis: str = "model",
    hot_tiers: Sequence[dict] | None = None,
    comm_free: Sequence[bool] | None = None,
    exec_mode: str = "auto",
    interpret: bool | None = None,
) -> jax.Array:
    """Every table's local pooled partial in ONE megakernel dispatch.

    Runs inside ``shard_map``.  The per-table loop of ``*_bag_partial`` calls
    becomes index arithmetic: this shard's local big-subtable shards (plus the
    replicated hot-tier segments) are concatenated into one packed buffer with
    a trailing all-zero row, and every access is *routed* instead of masked —

      hot row & my bag position  -> its hot-segment slot,
      cold row owned here        -> the local-shard segment,
      anything else              -> the zero row (contributes nothing),

    so the single ``ops.packed_multi_pooled`` call (Pallas megakernel on TPU,
    packed jnp oracle elsewhere) computes partials whose psum over ``axis``
    counts every contribution exactly once — the same math as the per-table
    partials, minus T-1 dispatches.  R LUTs (QR) are packed and spread across
    shards by bag position; TT outer cores are packed replicated.

    ``comm_free[t]`` marks tables whose params are full local replicas (the
    duplication planner's communication kill): every access is served locally
    and their output columns must be EXCLUDED from the caller's psum.
    Returns (B, T, dim) partials in the compute dtype.
    """
    from repro.core import packed_tables, tt_embedding
    from repro.kernels import ops

    emb0 = bags[0].emb
    kind = emb0.kind
    num_t = len(bags)
    compute = emb0.compute_dtype
    shard = jax.lax.axis_index(axis)
    nsh = plans[0].num_shards
    pooling = indices.shape[-1]
    cf = tuple(bool(c) for c in (comm_free or [False] * num_t))
    cf_b = jnp.asarray(cf)[None, :, None]
    pos_mine = ((jnp.arange(pooling, dtype=jnp.int32) % nsh) == shard)[None, None, :]

    segs = [tables[t][packed_tables.big_key(kind)] for t in range(num_t)]
    parts = list(segs)
    hot_sizes: list[int] = []
    if hot_tiers is not None:
        hots = [hot_tiers[t]["hot_table"] for t in range(num_t)]
        hot_sizes = [int(h.shape[0]) for h in hots]
        parts += hots
    width = int(segs[0].shape[1])
    layout = packed_tables.layout_for(bags)
    big = packed_tables.concat_with_zero(
        [packed_tables.stream_view(p, layout) for p in parts], compute
    )
    seg_sizes = [int(s.shape[0]) for s in segs]
    seg_off = np.cumsum([0] + seg_sizes)
    hot_off = seg_off[-1] + np.cumsum([0] + hot_sizes)
    zero_row = int(seg_off[-1] + sum(hot_sizes))
    seg_off_a = jnp.asarray(seg_off[:num_t], jnp.int32)[None, :, None]
    rps = jnp.asarray(
        [plans[t].rows_per_shard for t in range(num_t)], jnp.int32
    )[None, :, None]

    def route_big(big_idx: jax.Array) -> jax.Array:
        """Table-local big-subtable rows (B, T, K) -> packed stream rows."""
        local = big_idx - shard * rps
        owned = ((local >= 0) & (local < rps)) | cf_b
        local = jnp.where(cf_b, big_idx, local)          # replicas: global row
        stream = jnp.where(owned, seg_off_a + local, zero_row)
        if hot_tiers is not None:
            hot_slot = jnp.stack(
                [hot_tiers[t]["hot_slot"] for t in range(num_t)]
            )                                            # (T, big_rows)
            slot = hot_slot[jnp.arange(num_t)[None, :, None], big_idx]
            is_hot = slot >= 0
            hot_off_a = jnp.asarray(hot_off[:num_t], jnp.int32)[None, :, None]
            stream = jnp.where(
                is_hot, jnp.where(pos_mine, hot_off_a + slot, zero_row), stream
            )
        return stream

    miss = jnp.full(indices.shape, -1, jnp.int32)
    cache = jnp.zeros((1, width), compute)

    if kind == "qr":
        q_idx, r_idx = hashing.qr_decompose(indices, emb0.collision)
        r_segs = [tables[t]["r"] for t in range(num_t)]
        r_sizes = [int(r.shape[0]) for r in r_segs]
        r_off = np.cumsum([0] + r_sizes)
        r_packed = packed_tables.concat_with_zero(r_segs, compute)
        r_off_a = jnp.asarray(r_off[:num_t], jnp.int32)[None, :, None]
        # replicated LUT: spread across shards by bag position; comm-free
        # tables take every position (their column skips the psum)
        r_stream = jnp.where(
            pos_mine | cf_b, r_off_a + r_idx, int(r_off[-1])
        )
        out = ops.packed_multi_pooled(
            {"q": big, "r": r_packed, "cache": cache},
            {"q_idx": route_big(q_idx), "slot": miss, "r_idx": r_stream},
            kind="qr", exec_mode=exec_mode, interpret=interpret,
        )
    elif kind == "tt":
        spec = emb0.tt_spec
        i1, i2, i3 = tt_embedding.tt_decompose(indices, spec)
        t_ids = jnp.arange(num_t, dtype=jnp.int32)[None, :, None]
        g1 = jnp.concatenate(
            [tables[t]["g1"].astype(compute) for t in range(num_t)], axis=0
        )
        g3 = jnp.concatenate(
            [tables[t]["g3"].astype(compute) for t in range(num_t)], axis=0
        )
        out = ops.packed_multi_pooled(
            {"g1": g1, "g2": big, "g3": g3, "cache": cache},
            {
                "i1": i1 + t_ids * spec.v1,
                "i2": route_big(i2),
                "i3": i3 + t_ids * spec.v3,
                "slot": miss,
            },
            kind="tt", dims=(spec.d1, spec.d2, spec.d3, spec.rank),
            exec_mode=exec_mode, interpret=interpret,
        )
    else:
        out = ops.packed_multi_pooled(
            {"table": big, "cache": cache},
            {"idx": route_big(indices), "slot": miss},
            kind="dense", exec_mode=exec_mode, interpret=interpret,
        )

    scale = packed_tables.combiner_scale(bags, out.dtype)
    return (out * scale[None, :, None]).astype(compute)


def make_dup_hot_tiers(tables: Sequence[dict], bags: Sequence[BagConfig], dup_plan):
    """Hot-tier arrays per table from a DuplicationPlan.

    Returns one ``{"hot_table", "hot_slot"}`` dict per bag (uniform pytree so
    shard_map in_specs stay static); tables with nothing to replicate get a
    1-row dummy whose slot map never matches.
    """
    tiers = []
    for params, bag, tp in zip(tables, bags, dup_plan.tables):
        big = params.get("q", params.get("g2", params.get("table")))
        rows = tp.hot_plan.hot_slot.size
        if tp.comm_free or tp.hot_plan.num_hot == 0:
            tiers.append({
                "hot_table": jnp.zeros((1, big.shape[1]), big.dtype),
                "hot_slot": jnp.full((rows,), -1, jnp.int32),
            })
        else:
            tiers.append({
                "hot_table": big[jnp.asarray(tp.hot_plan.hot_rows, jnp.int32)],
                "hot_slot": jnp.asarray(tp.hot_plan.hot_slot, jnp.int32),
            })
    return tiers


# ---------------------------------------------------------------------------
# global wrappers
# ---------------------------------------------------------------------------

def shard_qr_params(
    params: dict, cfg: EmbeddingConfig, mesh: Mesh, *, row_axis: str = "model"
) -> dict:
    """Device-put QR params with the tiered layout's shardings."""
    out = {}
    if "q" in params:
        out["q"] = jax.device_put(
            pad_q_table(params["q"], cfg), NamedSharding(mesh, P(row_axis, None))
        )
        out["r"] = jax.device_put(params["r"], NamedSharding(mesh, P()))  # LUT tier
    elif "g2" in params:
        # TT: middle core row-sharded, outer cores duplicated (SRAM tier)
        out["g2"] = jax.device_put(
            pad_q_table(params["g2"], cfg), NamedSharding(mesh, P(row_axis, None))
        )
        out["g1"] = jax.device_put(params["g1"], NamedSharding(mesh, P()))
        out["g3"] = jax.device_put(params["g3"], NamedSharding(mesh, P()))
    else:
        out["table"] = jax.device_put(
            pad_q_table(params["table"], cfg), NamedSharding(mesh, P(row_axis, None))
        )
    return out


def build_token_embed(
    mesh: Mesh,
    cfg: EmbeddingConfig,
    *,
    batch_axis: str = "data",
    row_axis: str = "model",
    hot: bool = False,
):
    """Jitted token-embedding lookup (B, S) -> (B, S, dim), two-level scheme."""
    nsh = mesh.shape[row_axis]
    plan = ShardPlan(cfg, nsh)

    def local_fn(params, idx, tier):
        if cfg.kind == "qr":
            part = qr_token_partial(
                params["q"], params["r"], idx, plan, axis=row_axis,
                hot_table=None if tier is None else tier["hot_table"],
                hot_slot=None if tier is None else tier["hot_slot"],
            )
        else:
            part = _owned_rows_gather(
                params["table"].astype(cfg.compute_dtype), idx, plan, axis=row_axis
            )
        return jax.lax.psum(part, row_axis)

    tspec = {"q": P(row_axis, None), "r": P()} if cfg.kind == "qr" else {
        "table": P(row_axis, None)
    }
    in_specs = (
        tspec,
        P(batch_axis, None),
        None if not hot else {"hot_table": P(), "hot_slot": P()},
    )

    @jax.jit
    def fn(params, idx, tier=None):
        return jax.shard_map(
            local_fn, mesh=mesh, in_specs=in_specs,
            out_specs=P(batch_axis, None, None), check_vma=False,
        )(params, idx, tier)

    return fn


def token_embed_inline(params: dict, idx: jax.Array, cfg: EmbeddingConfig,
                       *, row_axis: str = "model") -> jax.Array:
    """Two-level GnR token embedding usable INSIDE a jitted model body.

    Reads the active mesh/rules from ``repro.distributed.sharding`` (set by
    the launcher's ``use_rules``); falls back to the plain lookup when no mesh
    is active or the row axis is absent. Differentiable: the backward pass is
    the transposed scatter-add into the local Q shard + psum, exactly the
    partial-reduce scheme in reverse.

    This is the paper's execution scheme as a drop-in for the GSPMD gather:
    the Q row is fetched only on its owner shard ("bank-group" locality), the
    replicated R add happens on one shard, and a single pooled psum combines —
    XLA's alternative would all-gather table rows to the data shards.
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed import sharding as SH

    mesh = SH.current_mesh()
    if mesh is None or row_axis not in mesh.shape or cfg.kind != "qr":
        from repro.core import qr_embedding

        return qr_embedding.lookup(params, idx, cfg)

    nsh = mesh.shape[row_axis]
    plan = ShardPlan(cfg, nsh)
    batch_spec = SH.spec_for(("batch",))[0]

    def local_fn(q_shard, r_full, idx_l):
        part = qr_token_partial(q_shard, r_full, idx_l, plan, axis=row_axis)
        return jax.lax.psum(part, row_axis)

    return jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(row_axis, None), P(), P(batch_spec, None)),
        out_specs=P(batch_spec, None, None),
        check_vma=False,
    )(params["q"], params["r"], idx)
