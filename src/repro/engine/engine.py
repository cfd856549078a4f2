"""compile() — turn an EmbeddingPlan into an executable EmbeddingEngine.

The engine is the ONE front door for every gather-and-reduce path in the
repo.  It owns the dispatch that used to be hand-wired per caller:

* ``lookup``            — single-chip multi-table GnR (packed megakernel on
                          packable sets, per-table loop otherwise; Pallas on
                          TPU, jnp oracles elsewhere).  Differentiable — the
                          kernel paths carry reference-recompute custom vjps,
                          so this is also the training entry.
* ``forward_partial``   — the sharded two-level GnR, run INSIDE ``shard_map``:
                          local partials (one megakernel dispatch when packed)
                          plus the pooled psum, with duplication-plan
                          comm-free tables skipping the combine.
* ``gnr``               — jitted global wrapper over ``forward_partial``
                          (replaces ``build_multi_bag_gnr`` /
                          ``build_dup_multi_bag_gnr``).
* ``inline_gnr``        — mesh-aware dispatch usable inside a jitted model
                          body (the DLRM forward): reads the active mesh and
                          picks single-chip vs two-level automatically.
* ``cached_lookup`` / ``pack`` / ``serve_gather`` — the batched serving path:
                          prefetch-scheduler slot maps routed through the
                          packed cache block, one jit keyed by the (hashable)
                          plan.
* ``baseline``          — the no-technique GSPMD reference (benchmarks diff
                          against it).

Engines are cheap to construct; ``engine_for(spec)`` memoizes the no-trace
plan+compile so model forwards can resolve their engine at trace time.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.core import embedding_bag, hashing, packed_tables
from repro.core import sharded_embedding as SE
from repro.engine.plan import EmbeddingPlan, plan as _plan
from repro.engine.spec import EngineSpec


# ---------------------------------------------------------------------------
# serving dispatch — module-level jit keyed by the STATIC (hashable) plan, so
# repeated sessions/benchmark repeats hit jax's compilation cache.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("plan", "interpret"))
def _serve_gather_jit(packed, idx, slot, cache_rows, plan: EmbeddingPlan,
                      interpret: bool | None = None):
    from repro.kernels import ops

    # Trace-time bump: a counter inside a jitted body counts *traces*, not
    # calls, so this is the compiled-program count for the serving dispatch.
    # The online re-planner's runtime-arg swaps must leave it at 1.
    obs.inc("engine/compile/serve_gather")
    layout = plan.layout
    streams = packed_tables.pack_indices(idx, layout)
    streams["slot"] = packed_tables.global_slots(slot, layout)
    # the cache-block gather IS the staging DMA (overlapped on hardware)
    cache = packed[packed_tables.big_key(layout.kind)][cache_rows]
    pooled = ops.packed_multi_pooled(
        {**packed, "cache": cache}, streams,
        kind=layout.kind, dims=layout.tt_dims, exec_mode=plan.spec.exec_backend,
        dim_block=plan.dim_block, interpret=interpret,
    )
    scale = packed_tables.combiner_scale(plan.bags, jnp.float32)
    return pooled * scale[None, :, None].astype(pooled.dtype)


class EmbeddingEngine:
    """Executable embedding layer compiled from an ``EmbeddingPlan``."""

    def __init__(self, plan: EmbeddingPlan):
        self.plan = plan
        self.spec = plan.spec
        self.bags = list(plan.spec.bags)
        # telemetry: the active plan's summary rides along with any metrics
        # snapshot taken while this engine serves (repro.obs is a no-op when
        # disabled, so plain compiles pay nothing).
        if obs.enabled():
            obs.attach("engine_plan", plan.summary())

    # -- single-chip / training entry ----------------------------------------

    def lookup(self, tables, indices, *, lengths=None, interpret=None):
        """All-tables GnR, (B, T, K) indices -> (B, T, dim).

        Packed plans run ONE megakernel dispatch (``packed_multi_pooled`` —
        Pallas on TPU, packed jnp oracle elsewhere, custom-vjp backed so
        ``jax.grad`` through this entry is exact); per-table plans run the
        semantic loop.  This is the training entry: DLRM's forward and the
        engine parity/grad tests differentiate straight through it.
        """
        obs.inc("engine/dispatch/lookup")
        if self.plan.packed:
            return packed_tables.packed_multi_bag_lookup(
                tables, indices, self.bags, lengths=lengths,
                exec_mode=self.spec.exec_backend, interpret=interpret,
                dim_block=self.plan.dim_block,
            )
        if lengths is not None:
            raise NotImplementedError("ragged bags need a packable bag set")
        return embedding_bag.multi_bag_lookup(tables, indices, self.bags)

    # -- sharded two-level GnR (inside shard_map) ----------------------------

    def forward_partial(
        self,
        tables,
        indices,
        *,
        num_shards: int | None = None,
        hot_tiers=None,
        axis: str | None = None,
        interpret=None,
    ):
        """Two-level GnR body: local partials + the pooled psum.

        Runs INSIDE ``shard_map`` over local shards.  Packed plans compute
        every table's local partial in one megakernel dispatch
        (``SE.packed_local_partial``); otherwise the per-kind partials run in
        a loop.  Duplication-plan comm-free tables are served entirely from
        local replicas and skip the psum (the paper's communication kill).
        """
        obs.inc("engine/dispatch/forward_partial")
        axis = axis or self.spec.row_axis
        nsh = num_shards or self.plan.num_shards
        bags = self.bags
        plans = [SE.ShardPlan(b.emb, nsh) for b in bags]
        cf = list(self.plan.comm_free)
        dup = self.plan.dup
        psum_cols = [t for t, c in enumerate(cf) if not c]

        if self.plan.packed:
            parts = SE.packed_local_partial(
                tables, indices, bags, plans, axis=axis,
                hot_tiers=hot_tiers, comm_free=cf if any(cf) else None,
                exec_mode=self.spec.exec_backend, interpret=interpret,
            )
            if len(psum_cols) == len(bags):
                return jax.lax.psum(parts, axis)
            if psum_cols:
                combined = jax.lax.psum(parts[:, psum_cols], axis)
                parts = parts.at[:, psum_cols].set(combined)
            return parts

        outs, needs_psum = [], []
        for t, (bag, tplan) in enumerate(zip(bags, plans)):
            idx = indices[:, t]
            params = tables[t]
            if cf[t]:
                # replicated everywhere -> full local lookup, no combine
                outs.append(embedding_bag.bag_lookup(params, idx, bag))
                needs_psum.append(False)
                continue
            tier = None if hot_tiers is None else hot_tiers[t]
            if bag.emb.kind == "qr":
                part = SE.qr_bag_partial(
                    params["q"], params["r"], idx, tplan, axis=axis,
                    hot_table=None if tier is None else tier["hot_table"],
                    hot_slot=None if tier is None else tier["hot_slot"],
                )
            elif bag.emb.kind == "tt":
                part = SE.tt_bag_partial(
                    params["g1"], params["g2"], params["g3"], idx, tplan,
                    axis=axis,
                    hot_table=None if tier is None else tier["hot_table"],
                    hot_slot=None if tier is None else tier["hot_slot"],
                )
            else:
                part = SE.dense_bag_partial(params["table"], idx, tplan, axis=axis)
            if bag.combiner == "mean":
                part = part / jnp.asarray(bag.pooling, part.dtype)
            outs.append(part)
            needs_psum.append(True)
        if all(needs_psum):
            return jax.lax.psum(jnp.stack(outs, axis=1), axis)
        if any(needs_psum):
            combined = jax.lax.psum(
                jnp.stack([o for o, n in zip(outs, needs_psum) if n], axis=1),
                axis,
            )
        res, si = [], 0
        for o, n in zip(outs, needs_psum):
            if n:
                res.append(combined[:, si])
                si += 1
            else:
                res.append(o)
        return jnp.stack(res, axis=1)

    # -- global (jitted) two-level GnR ---------------------------------------

    def _table_specs(self, bag, comm_free: bool, row_axis: str):
        if comm_free:
            keys = {"qr": ("q", "r"), "tt": ("g1", "g2", "g3")}.get(
                bag.emb.kind, ("table",)
            )
            return {k: P() for k in keys}
        if bag.emb.kind == "qr":
            return {"q": P(row_axis, None), "r": P()}
        if bag.emb.kind == "tt":
            return {"g1": P(), "g2": P(row_axis, None), "g3": P()}
        return {"table": P(row_axis, None)}

    def gnr(self, mesh: Mesh, *, hot: bool = False, interpret=None):
        """Jitted global GnR over all tables — the end-to-end two-level scheme.

        Returned fn: ``fn(tables, indices (B, T, K), hot_tiers=None)`` ->
        (B, T, dim).  Plans carrying a duplication plan serve comm-free
        tables from local replicas (replicated in_specs, no psum); ``hot``
        adds hot-tier specs on plain plans.  ``interpret`` as in
        ``ops.packed_multi_pooled``.
        """
        obs.inc("engine/dispatch/gnr_build")
        spec = self.spec
        row_axis, batch_axis = spec.row_axis, spec.batch_axis
        nsh = mesh.shape[row_axis]
        cf = self.plan.comm_free
        has_dup = self.plan.dup is not None
        with_tiers = hot or has_dup

        def local_fn(tables, indices, hot_tiers):
            return self.forward_partial(
                tables, indices, num_shards=nsh, hot_tiers=hot_tiers,
                axis=row_axis, interpret=interpret,
            )

        in_specs = (
            [self._table_specs(b, c, row_axis) for b, c in zip(self.bags, cf)],
            P(batch_axis, None, None),
            [{"hot_table": P(), "hot_slot": P()} for _ in self.bags]
            if with_tiers else None,
        )
        out_specs = P(batch_axis, None, None)

        @jax.jit
        def fn(tables, indices, hot_tiers=None):
            return jax.shard_map(
                local_fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                check_vma=False,
            )(tables, indices, hot_tiers)

        return fn

    def inline_gnr(self, tables, indices):
        """GnR usable INSIDE a jitted model body (the DLRM forward).

        Reads the active mesh/rules from ``repro.distributed.sharding`` (set
        by the launcher's ``use_rules``): no mesh or no row axis -> the
        single-chip ``lookup``; otherwise the two-level ``forward_partial``
        under ``shard_map``.  Differentiable on both paths.
        """
        obs.inc("engine/dispatch/inline_gnr")
        from repro.distributed import sharding as SH

        mesh = SH.current_mesh()
        row_axis = self.spec.row_axis
        if mesh is None or row_axis not in mesh.shape:
            return self.lookup(tables, indices)

        nsh = mesh.shape[row_axis]
        batch_spec = SH.spec_for(("batch",))[0]

        def local_fn(tabs, idx):
            return self.forward_partial(tabs, idx, num_shards=nsh, axis=row_axis)

        return jax.shard_map(
            local_fn,
            mesh=mesh,
            in_specs=(
                [self._table_specs(b, False, row_axis) for b in self.bags],
                P(batch_spec, None, None),
            ),
            out_specs=P(batch_spec, None, None),
            check_vma=False,
        )(tables, indices)

    # -- cached / packed serving path ----------------------------------------

    def cached_lookup(
        self, params, idx, table: int = 0, *, cache_rows=None, slot=None,
        interpret=None,
    ):
        """Single-chip cached GnR for one table — the serving path unit.

        Consumes the prefetch scheduler's staged state: ``cache_rows``
        (slots,) names the big-table rows resident this batch, ``slot``
        (..., K) routes each access (-1 = miss).  QR/dense run the
        ``cached_gather`` kernel; TT runs the fused TT bag kernel (outer
        cores already VMEM-pinned); hashed sets fall back to the plain bag.
        """
        obs.inc("engine/dispatch/cached_lookup")
        from repro.kernels import ops

        bag = self.bags[table]
        emb = bag.emb
        if emb.kind == "qr":
            q_idx, r_idx = hashing.qr_decompose(idx, emb.collision)
            cache = params["q"][cache_rows]
            out = ops.cached_qr_pooled(
                params["q"], cache, params["r"], q_idx, slot, r_idx,
                interpret=interpret, dim_block=self.plan.dim_block,
            )
        elif emb.kind == "tt":
            from repro.core import tt_embedding

            spec = emb.tt_spec
            i1, i2, i3 = tt_embedding.tt_decompose(idx, spec)
            out = ops.tt_pooled_auto(
                params["g1"], params["g2"], params["g3"], i1, i2, i3,
                dims=(spec.d1, spec.d2, spec.d3, spec.rank),
                exec_mode=emb.tt_exec, interpret=interpret,
            )
        elif emb.kind == "hashed":
            # k-ary expansion doesn't fit the single-row slot map; serve uncached
            return embedding_bag.bag_lookup(params, idx, bag)
        else:
            cache = params["table"][cache_rows]
            out = ops.cached_pooled(
                params["table"], cache, idx, slot, interpret=interpret,
                dim_block=self.plan.dim_block,
            )
        if bag.combiner == "mean":
            out = out / jnp.asarray(bag.pooling, out.dtype)
        return out

    def pack(self, tables: Sequence[dict]) -> dict:
        """Concatenate per-table params into the packed megakernel buffers."""
        if not self.plan.packed:
            raise ValueError("plan is not packed; no packed buffers to build")
        obs.inc("engine/dispatch/pack")
        return packed_tables.pack_params(tables, self.plan.layout)

    def serve_gather(self, packed, idx, slot, cache_rows):
        """One megakernel dispatch for a whole batch's embedding layer.

        ``packed`` from :meth:`pack`; ``idx`` (B, T, K) logical indices;
        ``slot`` (B, T, K) per-table scheduler slots (-1 = miss);
        ``cache_rows`` the packed cache block's global rows
        (``packed_tables.packed_cache_rows`` over the schedulers).  One jit
        keyed by the hashable plan — repeat sessions recompile nothing.
        """
        if not self.plan.packed:
            raise ValueError("plan is not packed; serve_gather needs a layout")
        obs.inc("engine/dispatch/serve_gather")
        return _serve_gather_jit(packed, idx, slot, cache_rows, self.plan)

    def packed_cache_rows(self, schedulers) -> "np.ndarray":
        """Per-table scheduler state -> the packed cache block's global rows."""
        if not self.plan.packed:
            raise ValueError("plan is not packed; no packed cache block exists")
        return packed_tables.packed_cache_rows(
            [s.cache_rows() for s in schedulers], self.plan.layout
        )

    def hot_tiers(self, tables: Sequence[dict]):
        """Duplication-plan hot-tier arrays (uniform pytree, one per table)."""
        if self.plan.dup is None:
            raise ValueError("plan has no duplication plan")
        return SE.make_dup_hot_tiers(tables, self.bags, self.plan.dup)

    def fresh_schedulers(self):
        return self.plan.fresh_schedulers()

    def summary(self) -> dict:
        return self.plan.summary()

    # -- baseline (benchmarks diff against this) ------------------------------

    def baseline(self, mesh: Mesh):
        """No-technique GSPMD baseline: plain gathers under auto-sharding.

        XLA materializes all-gathers of table rows; benchmarks diff its
        collective bytes / wall-time against :meth:`gnr`.
        """
        obs.inc("engine/dispatch/baseline_build")
        spec = self.spec
        bags = self.bags

        def fn(tables, indices):
            tables = [
                {
                    k: jax.lax.with_sharding_constraint(
                        v, NamedSharding(mesh, P(spec.row_axis, None))
                    )
                    for k, v in t.items()
                }
                for t in tables
            ]
            indices = jax.lax.with_sharding_constraint(
                indices, NamedSharding(mesh, P(spec.batch_axis, None, None))
            )
            return embedding_bag.multi_bag_lookup(tables, indices, bags)

        return jax.jit(fn)


def compile(plan: EmbeddingPlan) -> EmbeddingEngine:  # noqa: A001
    """EmbeddingPlan -> executable EmbeddingEngine."""
    return EmbeddingEngine(plan)


@functools.lru_cache(maxsize=64)
def _engine_for(spec: EngineSpec, num_shards: int) -> EmbeddingEngine:
    return compile(_plan(spec, num_shards=num_shards))


def engine_for(spec: EngineSpec, *, num_shards: int = 1) -> EmbeddingEngine:
    """Memoized no-trace plan+compile — the model-forward resolution path.

    Specs are hashable, so resolving an engine inside a jitted forward costs
    one dict lookup after the first trace.
    """
    return _engine_for(spec, num_shards)
