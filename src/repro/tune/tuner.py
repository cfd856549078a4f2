"""Trace-driven autotuner: profile -> fit cost model -> choose plan knobs.

Closes the loop ROADMAP names "the refactor that makes every future kernel
self-tuning": a short profiled trace is distilled into a
:class:`TraceProfile`, observed latencies of candidate knob settings fit the
per-backend linear :class:`~repro.tune.cost_model.KernelCostModel`, and
``plan(spec, trace, tuner=fit(...))`` ranks the whole knob space by
predicted latency and freezes the argmin into the ``EmbeddingPlan``.

Two observation backends (the byteprofile-analysis trace->cost-model->replay
idiom):

* ``mode="measure"`` — timed micro-runs of the real execution paths (the
  packed ``serve_gather`` megakernel / the per-table loop) on this host, at
  two batch sizes so the per-byte and per-dispatch terms separate;
* ``mode="hlo"``    — no accelerator needed: lower the jnp-oracle execution
  to optimized HLO, run the loop-aware analyzer
  (``launch/hlo_analysis``, shared with ``benchmarks/roofline``), and
  convert bytes/flops to time via the chip constants in ``launch/mesh``.

``mode="auto"`` picks ``measure`` on TPU and ``hlo`` elsewhere.  Fit results
are memoized to a JSON cache keyed by (spec digest, device kind, mode) with
host metadata recorded per entry, so tuning runs once per machine class.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import time
from typing import Sequence

import numpy as np

from repro.cache import duplication, intra_gnr, sram_cache
from repro.tune.cost_model import (
    FEATURES, CostSample, KernelCostModel, fit_cost_model, plan_features,
)
from repro.tune.knobs import Knobs, default_knobs, knob_space

# modeled per-launch host/dispatch overhead for the HLO cost oracle
DISPATCH_OVERHEAD_S = 5e-6


def spec_digest(spec) -> str:
    """Stable (cross-process) digest of a spec — the tuner-cache key half.

    ``hash(spec)`` is salted per interpreter, so the JSON cache keys on a
    sha1 of the spec's repr instead (frozen dataclasses repr

    deterministically)."""
    return hashlib.sha1(repr(spec).encode()).hexdigest()[:16]


def device_kind() -> str:
    import jax

    dev = jax.devices()[0]
    return str(getattr(dev, "device_kind", jax.default_backend()))


def run_metadata() -> dict:
    """Host/backend identity recorded on tuner-cache entries and benchmark
    rows, so entries are comparable across machines."""
    import jax

    return {
        "backend": jax.default_backend(),
        "device_kind": device_kind(),
        "jax_version": jax.__version__,
    }


def _bag_shaped(trace: np.ndarray, pooling: int) -> np.ndarray:
    trace = np.asarray(trace)
    if trace.ndim == 2:
        return trace
    n = trace.size - trace.size % pooling
    return trace[:n].reshape(-1, pooling)


_BIG_NAME = {"qr": "q", "tt": "g2"}


# ---------------------------------------------------------------------------
# trace profile: everything the cost model needs, distilled once per trace
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TableProfile:
    """Per-table distillation of the profiled trace."""

    rows: int                    # big-subtable row count
    row_bytes: int
    width_elems: int
    accesses_per_batch: float    # big-subtable fetches per serving batch
    counts: np.ndarray           # logical-row access profile (dup planning)
    values: np.ndarray           # analyzer prefetch values (slot waterfill)
    batches: list                # per-batch big-row streams (hit simulation)


class TraceProfile:
    """Workload statistics the feature computation reads.

    Hit-rate/staging curves are simulated lazily per (table, slot budget) on
    a bounded batch sample; duplication outcomes are re-planned lazily per
    candidate byte budget.  Both are memoized — the knob space revisits the
    same budgets many times.
    """

    def __init__(self, tables: list[TableProfile], *, batch: int,
                 num_shards: int, dim: int):
        self.tables = tables
        self.batch = batch
        self.num_shards = num_shards
        self.dim = dim
        self._hit_memo: dict = {}
        self._comm_memo: dict = {}
        self._bags = None

    @classmethod
    def from_trace(cls, spec, trace: Sequence[np.ndarray], *, batch: int = 32,
                   num_shards: int = 1, max_batches: int = 8) -> "TraceProfile":
        if len(trace) != spec.num_tables:
            raise ValueError(
                f"need one trace per table: {len(trace)} vs {spec.num_tables}"
            )
        tables = []
        for bag, tr in zip(spec.bags, trace):
            emb = bag.emb
            shaped = _bag_shaped(tr, bag.pooling)
            big = _BIG_NAME.get(emb.kind, "table")
            big_trace, rows, row_bytes = intra_gnr.subtable_traces(
                shaped, emb
            )[big]
            loc = intra_gnr.analyze_bags(big_trace, rows, row_bytes=row_bytes)
            from repro.core import placement

            counts = placement.profile_counts(shaped.reshape(-1), emb.vocab)
            n_batches = min(max_batches, max(1, big_trace.shape[0] // batch))
            batches = [
                big_trace[i * batch: (i + 1) * batch] for i in range(n_batches)
            ]
            tables.append(TableProfile(
                rows=rows,
                row_bytes=row_bytes,
                width_elems=row_bytes // 4,
                accesses_per_batch=float(batch * shaped.shape[1]),
                counts=counts,
                values=loc.prefetch_value().astype(np.float64),
                batches=batches,
            ))
        prof = cls(tables, batch=batch, num_shards=num_shards,
                   dim=spec.bags[0].emb.dim)
        prof._bags = list(spec.bags)
        return prof

    def hit_stats(self, t: int, slots: int) -> tuple[float, float]:
        """(hit rate, staged rows/batch) of table ``t`` at a slot budget."""
        key = (t, int(slots))
        if key not in self._hit_memo:
            tp = self.tables[t]
            if slots <= 0 or not tp.batches:
                self._hit_memo[key] = (0.0, 0.0)
            else:
                stats = sram_cache.simulate(
                    tp.batches, tp.rows, int(slots), tp.values
                )
                self._hit_memo[key] = (stats.hit_rate, stats.staged_per_batch)
        return self._hit_memo[key]

    def comm_bytes(self, spec, dup_budget_bytes: int) -> float:
        """Modeled cross-shard combine bytes per batch under a dup budget."""
        n = self.num_shards
        if n <= 1:
            return 0.0
        key = int(dup_budget_bytes)
        if key not in self._comm_memo:
            num_t = len(self.tables)
            if key <= 0:
                not_free = num_t
            else:
                dplan = duplication.plan_duplication(
                    self._bags or list(spec.bags),
                    [tp.counts for tp in self.tables],
                    num_shards=n, budget_bytes=key,
                )
                not_free = sum(1 for t in dplan.tables if not t.comm_free)
            vec = self.dim * 4
            self._comm_memo[key] = (
                self.batch * not_free * vec * (n - 1) / max(1, n)
            )
        return self._comm_memo[key]


# ---------------------------------------------------------------------------
# the tuner object plan() consumes
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Tuner:
    """Fitted cost models + the profile they were fitted against."""

    models: dict                      # backend -> KernelCostModel
    profile: TraceProfile | None
    source: str                       # measure | hlo
    metadata: dict
    samples: list = dataclasses.field(default_factory=list)
    digest: str = ""
    from_cache: bool = False

    def predict(self, spec, knobs: Knobs, *, profile: TraceProfile | None = None
                ) -> float:
        profile = profile or self.profile
        if profile is None:
            raise ValueError("tuner has no trace profile; pass profile=")
        model = self.models.get(knobs.backend)
        if model is None:
            model = next(iter(self.models.values()))
        return model.predict(plan_features(spec, knobs, profile))

    def rank(self, spec, *, packable: bool | None = None,
             backend: str | None = None,
             profile: TraceProfile | None = None) -> list:
        """Knob space ordered by predicted latency: [(knobs, seconds), ...]."""
        if packable is None:
            from repro.core import packed_tables

            packable = packed_tables.packable(spec.bags)
        space = knob_space(spec, packable=packable)
        if backend is not None:
            space = tuple(k for k in space if k.backend == backend) or space
        scored = [(k, self.predict(spec, k, profile=profile)) for k in space]
        scored.sort(key=lambda kp: kp[1])
        return scored

    def choose(self, spec, *, packable: bool | None = None,
               backend: str | None = None,
               profile: TraceProfile | None = None,
               tie_rel: float = 0.02) -> Knobs:
        """Argmin-predicted-latency knobs, with near-ties (within ``tie_rel``)
        resolved toward the heuristic default — the tuner only moves a knob
        when the model predicts a real win."""
        if packable is None:
            from repro.core import packed_tables

            packable = packed_tables.packable(spec.bags)
        ranked = self.rank(spec, packable=packable, backend=backend,
                           profile=profile)
        best_k, best_p = ranked[0]
        default = default_knobs(spec, packable=packable)
        if backend is not None and default.backend != backend:
            return best_k
        d_pred = self.predict(spec, default, profile=profile)
        if d_pred <= best_p * (1.0 + tie_rel):
            return default
        return best_k

    def describe(self) -> dict:
        """JSON form — the memo-cache entry / CI cost-model artifact."""
        return {
            "metadata": self.metadata,
            "source": self.source,
            "spec_digest": self.digest,
            "models": {b: m.describe() for b, m in self.models.items()},
            "samples": [s.describe() for s in self.samples],
        }


# ---------------------------------------------------------------------------
# observation: timed micro-runs / HLO-analyzed lowerings
# ---------------------------------------------------------------------------

def _time_call(fn, *args, warmup: int = 1, iters: int = 3) -> float:
    """Median wall seconds of a blocking call on this host."""
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def _batch_indices(spec, trace, batch: int, seed: int = 0):
    """(B, T, K) logical bag indices drawn from the profiled trace."""
    import jax.numpy as jnp

    cols = []
    for bag, tr in zip(spec.bags, trace):
        shaped = _bag_shaped(tr, bag.pooling)
        if shaped.shape[0] < batch:          # tile short traces
            reps = -(-batch // shaped.shape[0])
            shaped = np.tile(shaped, (reps, 1))
        cols.append(shaped[:batch])
    return jnp.asarray(np.stack(cols, axis=1).astype(np.int32))


def _serving_call(eng, tables, idx):
    """The executable + args a micro-run times: the packed ``serve_gather``
    (with a live prefetch schedule) when the plan carries a cache, the
    ``lookup`` entry otherwise."""
    import jax
    import jax.numpy as jnp

    from repro.engine import big_rows

    eplan = eng.plan
    if eplan.packed and eplan.has_cache:
        packed = eng.pack(tables)
        scheds = eng.fresh_schedulers()
        emb = eplan.bags[0].emb
        rows = np.stack(
            [np.asarray(big_rows(np.asarray(idx)[:, t], emb))
             for t in range(len(eplan.bags))], axis=1,
        )
        for t in range(len(eplan.bags)):
            scheds[t].prefetch(rows[:, t])
        slot = jnp.asarray(np.stack(
            [scheds[t].slots_for(rows[:, t], record=False)
             for t in range(len(eplan.bags))], axis=1,
        ))
        cache_rows = jnp.asarray(eng.packed_cache_rows(scheds))
        return (lambda p, i, s, c: eng.serve_gather(p, i, s, c),
                (packed, idx, slot, cache_rows))
    fn = jax.jit(lambda tabs, i: eng.lookup(tabs, i))
    return fn, (tables, idx)


def _measure_sample(spec, knobs: Knobs, trace, batch: int, *, repeats: int
                    ) -> float:
    """Per-batch seconds of one knob setting, timed on this host."""
    import jax

    from repro import engine as engine_mod
    from repro.core import embedding_bag as EB

    eplan = engine_mod.plan(spec, trace=trace, knobs=knobs, num_shards=1)
    eng = engine_mod.compile(eplan)
    tables = EB.init_tables(jax.random.PRNGKey(0), list(spec.bags))
    idx = _batch_indices(spec, trace, batch)
    fn, args = _serving_call(eng, tables, idx)
    return _time_call(fn, *args, iters=repeats)


def _hlo_sample(spec, knobs: Knobs, trace, batch: int) -> float:
    """Per-batch seconds of one knob setting, modeled from optimized HLO.

    Lowers the jnp-oracle execution (same math as the kernel path — the
    Pallas interpret lowering hides its body from HLO), analyzes bytes/flops
    with the loop-aware analyzer, and converts to time with the chip
    constants plus a per-dispatch overhead term.
    """
    import jax

    from repro import engine as engine_mod
    from repro.core import embedding_bag as EB
    from repro.launch import hlo_analysis
    from repro.launch.mesh import V5E, peaks

    spec_j = spec.replace(exec_backend="jnp")
    eplan = engine_mod.plan(spec_j, trace=trace, knobs=knobs, num_shards=1)
    eng = engine_mod.compile(eplan)
    tables = EB.init_tables(jax.random.PRNGKey(0), list(spec_j.bags))
    idx = _batch_indices(spec_j, trace, batch)
    fn, args = _serving_call(eng, tables, idx)
    text = jax.jit(fn).lower(*args).compile().as_text()
    h = hlo_analysis.analyze(text)
    dispatches = 1.0 if knobs.backend == "packed" else float(spec.num_tables)
    chip = peaks(V5E)                    # the modeled chip, not the host
    return (
        max(h["flops"] / chip.flops_bf16, h["bytes"] / chip.hbm_bw)
        + DISPATCH_OVERHEAD_S * dispatches
    )


# ---------------------------------------------------------------------------
# fit
# ---------------------------------------------------------------------------

def _sample_keys(space) -> list:
    """Distinct measurement settings: duplication only changes the modeled
    comm term (never a single-chip micro-run), so candidates are deduped on
    the execution-affecting knobs."""
    seen, keys = set(), []
    for k in space:
        key = (k.backend, k.dim_block, k.cache_slots, k.cache_slot_policy)
        if key not in seen:
            seen.add(key)
            keys.append(key)
    return keys


def fit(
    spec,
    trace: Sequence[np.ndarray],
    *,
    mode: str = "auto",
    batch: int = 32,
    num_shards: int = 1,
    max_samples: int = 12,
    repeats: int = 3,
    cache_path: str | None = None,
) -> Tuner:
    """Fit per-backend cost models for a spec from a profiled trace.

    ``mode="measure"`` times the real execution paths; ``"hlo"`` lowers the
    jnp oracle and prices the analyzer's bytes/flops (the no-accelerator
    path); ``"auto"`` measures on TPU, analyzes HLO elsewhere.  When
    ``cache_path`` holds a previous fit for (spec digest, device kind, mode),
    it is loaded instead of re-observing (``tuner.from_cache``).
    """
    import jax

    from repro.core import packed_tables

    if mode not in ("auto", "measure", "hlo"):
        raise ValueError(f"unknown tuner mode {mode!r}")
    source = mode
    if mode == "auto":
        source = "measure" if jax.default_backend() == "tpu" else "hlo"

    digest = spec_digest(spec)
    meta = run_metadata()
    cache_key = f"{digest}:{meta['device_kind']}:{source}"
    profile = TraceProfile.from_trace(
        spec, trace, batch=batch, num_shards=num_shards
    )

    if cache_path and os.path.exists(cache_path):
        with open(cache_path) as f:
            cache = json.load(f)
        if cache_key in cache:
            entry = cache[cache_key]
            models = {
                b: KernelCostModel.from_json(m)
                for b, m in entry["models"].items()
            }
            return Tuner(models=models, profile=profile, source=source,
                         metadata=entry.get("metadata", meta),
                         digest=digest, from_cache=True)

    packable = packed_tables.packable(spec.bags)
    space = knob_space(spec, packable=packable)
    keys = _sample_keys(space)
    if len(keys) > max_samples:
        stride = len(keys) / max_samples
        keys = [keys[int(i * stride)] for i in range(max_samples)]

    # measurement drops duplication (it only moves the modeled comm term) and
    # observes each setting at two batch sizes so per-byte and per-dispatch
    # costs separate in the fit.
    spec_m = spec.replace(duplication=False)
    small = max(4, batch // 2)
    profiles = {batch: TraceProfile.from_trace(spec_m, trace, batch=batch),
                small: TraceProfile.from_trace(spec_m, trace, batch=small)}

    samples: list[CostSample] = []
    for backend, bd, slots, policy in keys:
        k = Knobs(dim_block=bd, cache_slots=slots, cache_slot_policy=policy,
                  dup_budget_bytes=0, backend=backend)
        for b, prof in profiles.items():
            if source == "measure":
                sec = _measure_sample(spec_m, k, trace, b, repeats=repeats)
            else:
                sec = _hlo_sample(spec_m, k, trace, b)
            samples.append(CostSample(
                knobs=k, features=plan_features(spec_m, k, prof),
                measured_s=sec, source=source,
            ))

    models = {}
    for backend in sorted({s.knobs.backend for s in samples}):
        sub = [s for s in samples if s.knobs.backend == backend]
        model = fit_cost_model(sub, backend=backend, source=source)
        models[backend] = _with_comm_floor(model)

    tuner = Tuner(models=models, profile=profile, source=source,
                  metadata=meta, samples=samples, digest=digest)
    if cache_path:
        cache = {}
        if os.path.exists(cache_path):
            with open(cache_path) as f:
                cache = json.load(f)
        cache[cache_key] = tuner.describe()
        with open(cache_path, "w") as f:
            json.dump(cache, f, indent=1)
    return tuner


def _with_comm_floor(model: KernelCostModel) -> KernelCostModel:
    """Single-chip observations can never price the comm term (its feature
    column is zero there), so an unfitted comm coefficient falls back to the
    analytic ICI wire rate — ranking across duplication budgets stays
    meaningful."""
    from repro.launch.mesh import V5E, peaks

    idx = FEATURES.index("comm_bytes")
    if model.coef[idx] > 0:
        return model
    coef = list(model.coef)
    coef[idx] = 1.0 / (2 * peaks(V5E).ici_bw_per_link)
    return dataclasses.replace(model, coef=tuple(coef))
