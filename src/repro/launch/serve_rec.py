"""Batched DLRM recommendation serving — the ProactivePIM pipeline end-to-end.

Steady-state loop over a queued request stream:

1. **offline** (once): profile per-table traces, run the intra-GnR analyzer,
   waterfill the global cache-slot budget across tables by prefetch value
   (``cache_slot_policy="adaptive"``), and let the duplication planner decide
   which subtables are replicated per shard vs row-sharded — comm-free tables
   skip the cross-shard combine entirely.  All tables are packed into ONE
   row-major buffer (``repro.core.packed_tables``) with per-table row / LUT /
   cache-slot offsets;
2. **per batch** (the serving loop): while batch ``t`` executes, the prefetch
   hook stages batch ``t+1``'s highest-value big-table rows into the packed
   SRAM-cache model and batch ``t+1``'s packed gather is dispatched — the
   double buffer.  A batch's whole embedding layer is ONE
   ``packed_gather`` megakernel dispatch (hits route to the VMEM cache block,
   misses stream HBM rows) instead of one kernel per table, and the host only
   blocks at the tail of the stream (``--mode sequential`` keeps the
   one-batch-at-a-time baseline for parity checks and speedup measurement).

Telemetry (``repro.obs``): the warm-up batch that compiles gather + head is
timed separately (``compile_s``) and excluded from the steady-state window;
every steady-state batch records a latency sample, so results carry
p50/p95/p99 instead of a single wall-clock number, plus the per-batch traffic
accounting (cache hits, modeled HBM bytes, comm bytes killed by duplication).
``--metrics-json`` dumps the full metric registry.  Two traces, two views:

* ``--trace-out`` writes the fenced host-only Chrome-trace/Perfetto JSON of
  the stage spans (prefetch [cache_rank, cache_update] -> pack -> h2d ->
  dispatch -> device compute -> interact): each stage is fenced with
  ``block_until_ready`` for honest durations, which serializes the overlap
  pipeline, so never compare a traced run's QPS against an untraced one;
* ``--profile-dir`` writes an unfenced ``jax.profiler`` profile of the
  steady-state batches: the same spans as host annotations (``batch`` as
  the profiler's step marker) on one clock with the device ops, the gather
  megakernel among them by its ``pallas_call`` name
  (``packed_{dense,qr,tt}_bag``).  Load it in TensorBoard or Perfetto.

Usage (CPU smoke):
    PYTHONPATH=src python -m repro.launch.serve_rec --arch dlrm-qr --smoke
    PYTHONPATH=src python -m repro.launch.serve_rec --arch dlrm-tt --tiny \
        --metrics-json metrics.json --trace-out trace.json
    PYTHONPATH=src python -m repro.launch.serve_rec --arch dlrm-qr --tiny \
        --profile-dir profile/
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import engine as engine_mod
from repro import obs
from repro.configs import registry
from repro.core import packed_tables
from repro.data import synthetic
from repro.engine import EngineSpec, big_rows, big_subtable  # noqa: F401 (re-export)
from repro.launch import compile_cache
from repro.models import dlrm
from repro.obs import attribution as obs_attribution
from repro.obs import report as obs_report
from repro.obs import traffic as obs_traffic


@dataclasses.dataclass
class ServeState:
    """The offline pass's output, built once per session and reusable across
    pipeline runs (schedulers are stateful, so ``run_pipeline`` constructs a
    fresh set from the plan per run).

    A thin view over the engine's ``EmbeddingPlan``: the legacy field names
    (``plan`` = the duplication plan, ``layout``, ``slot_budgets``, ...) are
    kept for the benchmarks and tests that read them.  When the plan came
    from a fitted tuner, ``predicted_s`` carries the cost model's per-batch
    latency prediction and ``drift`` accumulates predicted-vs-measured
    residuals across every pipeline run on this state (the online
    re-fit trigger).
    """

    engine: engine_mod.EmbeddingEngine
    predicted_s: float | None = None
    drift: obs.DriftMonitor | None = None

    @property
    def eplan(self) -> engine_mod.EmbeddingPlan:
        return self.engine.plan

    @property
    def bags(self) -> list:
        return self.engine.bags

    @property
    def plan(self):                          # the duplication plan
        return self.eplan.dup

    @property
    def locs(self) -> list[dict]:            # per-table intra-GnR analyses
        return list(self.eplan.locality)

    @property
    def values(self) -> list[np.ndarray]:    # per-table prefetch values
        return list(self.eplan.values)

    @property
    def layout(self):
        return self.eplan.layout

    @property
    def slot_budgets(self) -> list[int]:
        return list(self.eplan.slot_budgets)

    def fresh_schedulers(self, chips: int = 1):
        return self.engine.fresh_schedulers(chips)


def build_serve_state(cfg, *, shards: int, alpha: float, seed: int,
                      profile_n: int = 50_000, tuner=None,
                      knobs=None) -> ServeState:
    """Offline pass, one ``engine.plan`` call: profile -> analyze -> slot
    waterfill -> dup plan -> packed layout, compiled into the serving engine.

    ``tuner`` (a fitted ``repro.tune.Tuner``) or an explicit ``knobs`` routes
    the plan through the cost-model argmin instead of the heuristics; the
    serving pipeline needs the packed backend, so tuner choices are
    constrained to it.  A tuner also arms the drift monitor: its per-batch
    latency prediction for the chosen knobs is compared against measured
    batches while serving.
    """
    # per-table request streams: each sparse feature sees its own skew
    traces = [
        synthetic.zipf_trace(
            cfg.vocab_per_table, profile_n, alpha=alpha, seed=seed + 7 + t
        )
        for t in range(cfg.num_tables)
    ]
    spec = EngineSpec.from_dlrm(cfg, serving=True)
    predicted_s = drift = None
    if knobs is None and tuner is not None:
        knobs = tuner.choose(spec, backend="packed")
        predicted_s = tuner.predict(spec, knobs)
        drift = obs.DriftMonitor()
    eplan = engine_mod.plan(spec, num_shards=shards, trace=traces, knobs=knobs)
    return ServeState(engine=engine_mod.compile(eplan),
                      predicted_s=predicted_s, drift=drift)


def init_params(cfg, seed: int, chips: int = 1) -> dict:
    """The DLRM's weights from ``seed``.  With ``chips`` > 1 they are made in
    place on ``Mesh(devices[:chips], (row_axis,))``: every table's big
    subtable (dense table, QR quotient table) split by rows over the chips,
    everything else whole on each chip — the layout ``run_pipeline`` serves
    row-sharded."""
    key = jax.random.PRNGKey(seed)
    if chips == 1:
        return dlrm.init_dlrm(key, cfg)[0]
    devices = jax.devices()
    if len(devices) < chips:
        raise ValueError(f"--chips {chips}: JAX finds {len(devices)} devices")
    axis = EngineSpec.row_axis
    mesh = Mesh(np.asarray(devices[:chips]), (axis,))
    rows, whole = NamedSharding(mesh, P(axis, None)), NamedSharding(mesh, P())
    big = packed_tables.big_key(cfg.embedding_kind)
    make = lambda k: dlrm.init_dlrm(k, cfg)[0]  # noqa: E731
    shapes = jax.eval_shape(make, key)
    shard = {**jax.tree.map(lambda _: whole,
                            {k: v for k, v in shapes.items() if k != "tables"}),
             "tables": [{k: rows if k == big else whole for k in t}
                        for t in shapes["tables"]]}
    return jax.jit(make, out_shardings=shard)(key)


# The interaction + MLP head.  The pooled buffer is not donated: no output
# has its (B, T, dim) shape, so the TPU compiler finds no use for it and only
# warns ("Some donated buffers were not usable").
@functools.partial(jax.jit, static_argnames=("cfg",))
def _head_jit(params, dense, pooled, cfg):
    return dlrm.forward_from_pooled(params, dense, pooled, cfg)


def make_packed_gather(params, state: ServeState):
    """One jitted megakernel dispatch for a whole batch's embedding layer.

    Packs the tables once (device-side; tables row-sharded over a mesh are
    packed chip by chip); per batch the caller passes the
    logical indices, the per-table local slot maps, and the scheduler's packed
    cache rows — the cache-block gather ``big[cache_rows]`` *is* the staging
    DMA, overlapped (on hardware) with the previous batch.  The dispatch is
    ``EmbeddingEngine.serve_gather`` — one module-level jit keyed by the
    hashable plan, so repeated sessions hit jax's compilation cache.
    """
    eng = state.engine
    with obs.span("pack_tables", cat="offline"):
        packed = eng.pack(params["tables"])

    def gather(idx, slot, cache_rows):
        return eng.serve_gather(packed, idx, slot, cache_rows)

    return gather


# serving-record percentiles come from the same exact-quantile helper the
# obs histograms use (obs.metrics.exact_percentile) — one definition, so a
# metrics snapshot and a result record can never disagree.
_percentiles = obs.latency_percentiles


def run_pipeline(cfg, *, batch: int = 16, batches: int = 6, alpha: float = 1.05,
                 shards: int = 4, seed: int = 0, mode: str = "overlap",
                 state: ServeState | None = None, params=None,
                 fence: bool = False, profile_dir: str | None = None) -> dict:
    """Serve ``batches`` queued request batches; returns logits + measured QPS
    + the per-batch latency distribution + the traffic accounting.

    ``mode="overlap"``: double-buffered — batch ``t+1``'s prefetch + packed
    gather are dispatched while batch ``t``'s interaction/MLP head runs, and
    the host blocks only at the tail of the stream.
    ``mode="sequential"``: the baseline — gather, head, block, every batch.
    Both modes produce identical logits (asserted by the tier-1 suite); the
    QPS difference is the pipeline win.

    Batch 0 compiles gather + head; it is timed as ``compile_s`` and excluded
    from the steady-state window — ``qps`` covers post-warm-up batches only.
    Per-batch latency samples: sequential mode measures full request latency
    (dispatch to synced logits); overlap mode measures the pipeline's batch
    cycle time (the tail drain folds into the last sample).  ``fence=True``
    (set by ``--trace-out``) syncs after every stage so the trace spans carry
    device time — it serializes the overlap pipeline, perturbing QPS.
    ``profile_dir`` runs the steady-state batches (not batch 0) under
    ``jax.profiler.trace(profile_dir)``.

    Each batch's ``prefetch`` runs the schedulers phase by phase, every
    table's ``rank`` in a ``cache_rank`` span, then every table's
    ``update`` in a ``cache_update`` span (the tables are independent, so
    the slots are those of table-by-table ``prefetch``); ``dispatch`` spans
    carry the megakernel's ``grid_steps``, the ``chips`` it runs on, the
    entries a chip's kernel ``walked`` (pad bags included) and the entries
    the busiest chip ``owned``.

    Tables row-sharded over a mesh (``init_params`` with ``chips`` > 1)
    are served on its chips: each chip gathers from its own rows and cache
    block, and a ``reduce`` span enqueues the sum of the chips' pooled
    partials before the head.
    """
    if params is None:
        params, _ = dlrm.init_dlrm(jax.random.PRNGKey(seed), cfg)
    if state is None:
        state = build_serve_state(cfg, shards=shards, alpha=alpha, seed=seed)
    bags = state.bags
    eng = state.engine
    mesh = eng.row_mesh(params["tables"])
    chips = 1 if mesh is None else mesh.shape[eng.spec.row_axis]
    scheds = state.fresh_schedulers(chips)    # per-run cache state
    emb = bags[0].emb

    data = [
        synthetic.dlrm_batch(cfg, batch, seed=seed, step=t, alpha=alpha)
        for t in range(batches)
    ]
    idx_np = [np.asarray(b["idx"]) for b in data]
    rows_np = [
        np.stack([big_rows(idx_np[t][:, i], emb) for i in range(cfg.num_tables)],
                 axis=1)
        for t in range(batches)
    ]                                          # (B, T, K) big-subtable rows

    gather = make_packed_gather(params, state)
    grid_steps, walked = eng.grid_steps(batch), eng.walked(batch)
    if mesh is None:
        owned = [idx.size for idx in idx_np]
        put = jnp.asarray
        put_rows = jnp.asarray
    else:
        # entries the busiest chip owns: its share of each big subtable's
        # rows is the shard layout's (uniform over a packed set)
        rps = packed_tables.shard_layout(eng.plan.layout, chips).rows_per_table[0]
        owned = [int(np.bincount((r // rps).ravel(), minlength=chips).max())
                 for r in rows_np]
        whole = NamedSharding(mesh, P())
        by_chip = NamedSharding(mesh, P(eng.spec.row_axis))
        put = functools.partial(jax.device_put, device=whole)
        put_rows = functools.partial(jax.device_put, device=by_chip)

    def head(params, dense, pooled):
        return _head_jit(params, dense, pooled, cfg)

    def prefetch(t: int) -> None:
        rows = rows_np[t]
        with obs.span("prefetch", batch=t):
            with obs.span("cache_rank", batch=t):
                want = [s.rank(rows[:, i]) for i, s in enumerate(scheds)]
            with obs.span("cache_update", batch=t) as sp:
                before = _moved(scheds) if obs.enabled() else None
                for s, w in zip(scheds, want):
                    s.update(w)
                if before is not None:
                    sp.set(**{k: v - before[k]
                              for k, v in _moved(scheds).items()})

    def dispatch_gather(t: int):
        """Translate batch t through the slot maps and enqueue its megakernel."""
        with obs.span("pack", batch=t):        # host-side slot translation
            slot = np.stack(
                [scheds[i].slots_for(rows_np[t][:, i])
                 for i in range(cfg.num_tables)],
                axis=1,
            )
            cache_rows = eng.packed_cache_rows(scheds, chips)
        with obs.span("h2d", batch=t):         # host-to-device index upload
            args = (put(idx_np[t]), put(slot), put_rows(cache_rows))
        with obs.span("dispatch", batch=t, grid_steps=grid_steps, chips=chips,
                      walked=walked, owned=owned[t]):
            pooled = gather(*args)             # megakernel enqueue
        if mesh is not None:
            with obs.span("reduce", batch=t):  # the chips' partials summed
                pooled = eng.reduce_pooled(pooled, mesh)
        if fence:
            with obs.span("device_compute", batch=t):
                jax.block_until_ready(pooled)
        return pooled

    def interact(t: int, pooled):
        with obs.span("interact", batch=t):    # pairwise dot + MLP head
            out = head(params, data[t]["dense"], pooled)
        if fence:
            with obs.span("device_head", batch=t):
                jax.block_until_ready(out)
        return out

    logits: list = [None] * batches
    lats: list[float] = []
    # warm-up: batch 0 compiles gather + head — timed apart from steady state
    tc = time.perf_counter()
    with obs.span("compile_warmup", cat="offline"):
        prefetch(0)                        # cold-start staging for batch 0
        warm = interact(0, dispatch_gather(0))
        jax.block_until_ready(warm)
    compile_s = time.perf_counter() - tc
    logits[0] = np.asarray(warm)

    profiler = (jax.profiler.trace(profile_dir,
                                   profiler_options=_profile_options())
                if profile_dir else contextlib.nullcontext())
    with profiler:
        t0 = time.perf_counter()
        if mode == "overlap":
            if batches > 1:
                prefetch(1)
                pooled = dispatch_gather(1)
            prev = time.perf_counter()
            for t in range(1, batches):
                # enqueue batch t's head, then stage + dispatch batch t+1's
                # gather while it runs; block only at the tail of the stream
                with obs.span("batch", batch=t, mode=mode):
                    out = interact(t, pooled)
                    if t + 1 < batches:
                        prefetch(t + 1)
                        pooled = dispatch_gather(t + 1)
                    logits[t] = out
                if t < batches - 1:            # cycle time: enqueue-to-enqueue
                    now = time.perf_counter()
                    lats.append(now - prev)
                    prev = now
                    obs.observe_batch(batch=t, mode=mode, latency_s=lats[-1])
            with obs.span("tail_sync", mode=mode):
                jax.block_until_ready(logits[-1] if batches > 1 else warm)
            if batches > 1:                    # last cycle includes the drain
                lats.append(time.perf_counter() - prev)
                obs.observe_batch(batch=batches - 1, mode=mode,
                                  latency_s=lats[-1])
            logits = [np.asarray(x) for x in logits]
        elif mode == "sequential":
            for t in range(1, batches):
                tb = time.perf_counter()
                with obs.span("batch", batch=t, mode=mode):
                    prefetch(t)
                    pooled = dispatch_gather(t)
                    out = interact(t, pooled)
                    with obs.span("block", batch=t):
                        jax.block_until_ready(out)     # per-batch sync: the baseline
                lats.append(time.perf_counter() - tb)
                logits[t] = np.asarray(out)
                obs.observe_batch(batch=t, mode=mode, latency_s=lats[-1])
        else:
            raise ValueError(f"unknown mode {mode!r}")
        wall_s = time.perf_counter() - t0

    for lat in lats:                       # the SLO histograms (when enabled)
        obs.observe(f"serve/{mode}/batch_latency_s", lat)
    obs.observe(f"serve/{mode}/compile_s", compile_s)
    obs.inc(f"serve/{mode}/batches", len(lats))
    obs.inc(f"serve/{mode}/requests", batch * len(lats))
    if state.drift is not None and state.predicted_s is not None:
        for lat in lats:
            state.drift.observe(state.predicted_s, lat)

    served = batch * max(0, batches - 1)
    stats = [s.stats for s in scheds]
    hits = sum(s.hits for s in stats)
    acc = sum(s.accesses for s in stats)
    staged = sum(s.staged_rows for s in stats) / max(1, batches)
    report = obs_traffic.collect(state.eplan, scheds, batch=batch)
    if obs.enabled():
        obs.trace_counter(f"serve/{mode}/hit_rate", hit_rate=report.hit_rate)
    return {
        "config": cfg.name,
        "mode": mode,
        "batch": batch,
        "batches": batches,
        "served": served,
        "compile_s": compile_s,            # warm-up/compile, excluded from qps
        "wall_s": wall_s,
        "qps": served / max(wall_s, 1e-9),
        **_percentiles(lats),
        "latencies_s": lats,
        "hit_rate": hits / max(1, acc),
        "staged_per_batch": staged,
        "slot_budgets": list(state.slot_budgets),
        "traffic": report.describe(),
        "traffic_report": report,          # the live object (attribution joins)
        "drift": state.drift.summary() if state.drift is not None else None,
        "logits": logits,
    }


def _profile_options():
    """The profiler without its Python tracer, which would slow the host loop
    it records: the loop's own spans mark the host side."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def _moved(scheds) -> dict:
    """Rows staged, kept and evicted so far, summed over the schedulers."""
    stats = [s.stats for s in scheds]
    return {"staged": sum(x.staged_rows for x in stats),
            "kept": sum(x.kept_rows for x in stats),
            "evicted": sum(x.evicted_rows for x in stats)}


# result keys dropped from the --json / --metrics-json records (bulk arrays
# and live objects)
_RECORD_DROP = ("logits", "latencies_s", "traffic_report")


# -- resilient front-end mode (--frontend) ------------------------------------

_DEFAULT_ARRIVAL = "rate=400,horizon=3,deadline_ms=250"
_DEFAULT_FRONTEND_SLO = ("p99_ms=60,objective=0.99,fast_window=4,"
                         "slow_window=8,name=frontend")


def run_frontend(cfg, state, params, args, slo_engine=None) -> dict:
    """The ``--frontend`` serving session: open-loop traffic through the
    admission queue, fault injector, and degradation ladder.

    Returns the front end's report with the arrival/fault specs (seeds
    included) stamped in, so a saved record reproduces the run exactly.
    """
    from repro import serve

    aspec = serve.ArrivalSpec.parse(args.arrival or _DEFAULT_ARRIVAL)
    if args.seed and aspec.seed == 0:      # --seed flows into the traffic
        aspec = dataclasses.replace(aspec, seed=args.seed)
    fspec = serve.FaultSpec.parse(args.faults) if args.faults else serve.FaultSpec()
    if slo_engine is None:
        slo_engine = obs.SLOEngine(obs.SLOSpec.parse(_DEFAULT_FRONTEND_SLO))
    fcfg = serve.FrontendConfig(
        batch_size=args.batch or (8 if args.tiny else 16),
        queue_cap=args.queue_cap,
        shed_policy=args.shed_policy,
        queue_order=args.queue_order,
        # adaptation adapts *pinned* residency; the oracle prefetcher would
        # self-heal under drift and mask what the controller does
        residency="pinned" if args.adapt else "prefetch",
        service_mode=args.service_mode,
    )
    adapt_ctl = None
    if args.adapt:
        from repro.adapt import AdaptController

        adapt_ctl = AdaptController(state.eplan, seed=args.seed)
    frontend = serve.Frontend(
        cfg, fcfg, state, params,
        slo=slo_engine, faults=serve.FaultInjector(fspec),
        adapt=adapt_ctl,
    )
    requests = serve.generate(aspec, cfg)
    report = frontend.run(requests)
    report["arrival"] = aspec.describe()
    report["faults"] = fspec.describe()
    report["config"] = cfg.name
    report["mode"] = "frontend"

    req = report["requests"]
    print(
        f"[frontend] {req['generated']} requests over {aspec.horizon_s:.1f}s "
        f"(virtual): served {req['served']}, deadline-missed "
        f"{req['deadline_missed']}, shed {req['shed_total']} "
        f"(reject {req['shed_reject']} / evict {req['shed_evict']} / "
        f"shed-mode {req['shed_mode']} / abandoned {req['abandoned']}), "
        f"unaccounted {req['unaccounted']}"
    )
    print(
        f"[frontend] request latency p50={report['req_lat_p50_s'] * 1e3:.1f}ms "
        f"p95={report['req_lat_p95_s'] * 1e3:.1f}ms "
        f"p99={report['req_lat_p99_s'] * 1e3:.1f}ms (virtual), "
        f"miss rate {report['deadline_miss_rate']:.3f}, "
        f"shed rate {report['shed_rate']:.3f}, "
        f"hit rate {report['hit_rate']:.3f}"
    )
    deg = report["degrade"]
    for tr in deg["transitions"]:
        print(f"[degrade] batch {tr['at_batch']} t={tr['t_s']:.2f}s "
              f"{tr['from']} -> {tr['to']} ({tr['reason']})")
    ttr = report["time_to_recover_s"]
    print(
        f"[degrade] final rung {deg['rung']}, "
        f"{len(deg['transitions'])} transitions, time-to-recover "
        f"{'%.2fs' % ttr if ttr is not None else 'n/a'}"
    )
    if adapt_ctl is not None:
        print(f"[adapt] {adapt_ctl.batch_i} batches sketched, "
              f"events {report['adapt']['events'] or '{}'}")
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True,
                    help="dlrm config id (dlrm-qr | dlrm-tt | dlrm-dense)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: --smoke config with batch=8")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--batches", type=int, default=6)
    ap.add_argument("--alpha", type=float, default=1.05)
    ap.add_argument("--shards", type=int, default=4,
                    help="modeled row-shard count for the duplication plan "
                         "(a planning input only; nothing is placed)")
    ap.add_argument("--chips", type=int, default=1,
                    help="serve on this many devices: the tables are made "
                         "row-sharded over them and each chip gathers from "
                         "its own rows (dense and QR tables); unlike "
                         "--shards, which only sizes the modeled "
                         "duplication plan, this places the weights")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mode", default="overlap",
                    choices=["overlap", "sequential", "both"])
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write measured QPS / latency / hit-rate records")
    ap.add_argument("--plan-json", default=None, metavar="PATH",
                    help="write the EmbeddingPlan summary as JSON (CI artifact)")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="enable telemetry; write the metric registry "
                         "(latency histograms, dispatch counters, traffic)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="enable telemetry; write a Chrome-trace JSON of the "
                         "stage spans (fences every stage — perturbs overlap)")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="enable telemetry; run the steady-state batches "
                         "unfenced under jax.profiler.trace(DIR): host spans "
                         "and device ops on one timeline")
    ap.add_argument("--slo", default=None, metavar="SPEC",
                    help="serving SLO, e.g. 'p99_ms=50,hit=0.5,qps=100,"
                         "objective=0.99' — enables telemetry, burn-rate "
                         "alerts, and the flight recorder")
    ap.add_argument("--report", default=None, metavar="PATH",
                    help="write the serving-report artifact (markdown + JSON "
                         "twin): SLO state, per-stage attribution, traffic. "
                         "Enables telemetry and fences stages like --trace-out")
    ap.add_argument("--flight-dir", default=None, metavar="DIR",
                    help="directory for flight-recorder JSON dumps (written "
                         "when an SLO burns or a latency sample is anomalous)")
    ap.add_argument("--frontend", action="store_true",
                    help="serve open-loop traffic through the resilient "
                         "front end (admission queue + deadline batching + "
                         "fault injection + degradation ladder)")
    ap.add_argument("--arrival", default=None, metavar="SPEC",
                    help="traffic model, e.g. 'rate=400,horizon=3,"
                         "deadline_ms=250,flash=1.0+0.5x8,drift_s=1,seed=0'")
    ap.add_argument("--faults", default=None, metavar="SPEC",
                    help="fault schedule, e.g. 'stall@1.0:0.5,drop@1.5,"
                         "replica@2.0:1.0,gather@3.0:2,retries=3'")
    ap.add_argument("--shed-policy", default="reject_new",
                    choices=["reject_new", "drop_oldest"],
                    help="load-shedding policy at a full admission queue")
    ap.add_argument("--queue-cap", type=int, default=64,
                    help="admission queue bound (requests)")
    ap.add_argument("--service-mode", default="measured",
                    choices=["measured", "fixed"],
                    help="virtual service time: calibrated from measured "
                         "wall ('measured') or exactly one unit per batch "
                         "('fixed' — the deterministic CI configuration)")
    ap.add_argument("--queue-order", default="fifo", choices=["fifo", "edf"],
                    help="admission-queue dispatch order: arrival order or "
                         "deadline-earliest-first")
    ap.add_argument("--adapt", action="store_true",
                    help="online adaptation (repro.adapt): frequency "
                         "sketches + incremental re-pinning; standalone it "
                         "runs the pinned adaptive session, with --frontend "
                         "it feeds the admission loop's schedulers")
    ap.add_argument("--drift", default=None, metavar="SPEC",
                    help="batch-indexed hot-set drift for the --adapt "
                         "session, e.g. 'period=8,frac=0.25' (rotations "
                         "every `period` batches)")
    args = ap.parse_args(argv)
    if args.profile_dir and (args.trace_out or args.report or args.frontend
                             or args.adapt):
        ap.error("--profile-dir profiles the unfenced pipeline modes: "
                 "drop --trace-out, --report, --frontend and --adapt")
    if args.chips > 1 and (args.frontend or args.adapt):
        ap.error("--chips serves through the pipeline modes: drop "
                 "--frontend and --adapt")
    compile_cache.enable()

    telemetry = bool(args.metrics_json or args.trace_out or args.slo
                     or args.report or args.flight_dir or args.profile_dir)
    if telemetry:
        obs.enable()
    # --report needs device-honest stage durations for attribution, so it
    # fences like --trace-out (and carries the same QPS caveat).
    fence = bool(args.trace_out or args.report)

    slo_engine = recorder = None
    if args.slo:
        slo_engine = obs.SLOEngine(obs.SLOSpec.parse(args.slo))
    if args.slo or args.flight_dir or args.report:
        recorder = obs.FlightRecorder(out_dir=args.flight_dir)
    if slo_engine is not None or recorder is not None:
        # after enable(): the telemetry join cursors into the live registry.
        # In --frontend mode the front end feeds the SLO engine itself, so
        # the observatory carries only the recorder (no double observation).
        obs.install_observatory(
            slo=None if args.frontend else slo_engine, recorder=recorder,
        )

    name = f"{args.arch}-smoke" if (args.smoke or args.tiny) else args.arch
    cfg = registry.get_dlrm(name)
    batch = args.batch or (8 if args.tiny else 16)
    params = init_params(cfg, args.seed, args.chips)
    state = build_serve_state(
        cfg, shards=args.shards, alpha=args.alpha, seed=args.seed
    )
    emb = state.bags[0].emb
    big_name, _rows = big_subtable(emb)
    plan = state.plan
    if args.plan_json:
        with open(args.plan_json, "w") as f:
            json.dump(state.engine.summary(), f, indent=1)
        print(f"# wrote EmbeddingPlan summary to {args.plan_json}")
    print(
        f"{cfg.name}: {cfg.num_tables} tables, kind={cfg.embedding_kind}, "
        f"slot budgets {min(state.slot_budgets)}..{max(state.slot_budgets)} "
        f"({cfg.cache_slot_policy}), dup budget {cfg.dup_budget_mb} MiB, "
        f"packed rows {state.layout.total_rows}"
    )
    print(
        f"duplication plan: replicated {plan.replicated_bytes} B/chip, "
        f"comm_free={plan.comm_free}, local_share="
        f"{plan.tables[0].local_share:.2f}, "
        f"intra-GnR reuse[{big_name}]={state.locs[0][big_name].mean_intra_reuse:.2f}"
    )

    if args.frontend:
        report = run_frontend(cfg, state, params, args, slo_engine=slo_engine)
        if recorder is not None and recorder.dumps:
            for d in recorder.dumps:
                print(f"[flight] dumped {d['records']} records "
                      f"({d['reason']}) -> {d.get('path', '<memory>')}")
            report["flight_dumps"] = [
                {k: v for k, v in d.items() if k != "context"}
                for d in recorder.dumps
            ]
        if args.json:
            with open(args.json, "w") as f:
                json.dump([report], f, indent=1)
            print(f"# wrote frontend record to {args.json}")
        if args.metrics_json:
            snap = obs.snapshot().to_json()
            snap["config"] = cfg.name
            snap["frontend"] = report
            with open(args.metrics_json, "w") as f:
                json.dump(snap, f, indent=1)
            print(f"# wrote metric registry to {args.metrics_json}")
        return 0

    if args.adapt:
        from repro.adapt import DriftSchedule
        from repro.adapt.loop import serve_adaptive

        schedule = (DriftSchedule.parse(args.drift) if args.drift
                    else DriftSchedule(seed=args.seed))
        res = serve_adaptive(
            cfg, batch=batch, batches=args.batches, alpha=args.alpha,
            seed=args.seed, state=state, params=params,
            schedule=schedule, refit=True,
        )
        print(
            f"[adaptive] served {res['served']} requests in "
            f"{res['wall_s']:.2f}s -> {res['qps']:.1f} QPS, hit rate "
            f"{res['hit_rate']:.3f} (pinned residency)"
        )
        hs = res["hit_series"]
        print(f"[adaptive] hit-rate trajectory first->last: "
              f"{hs[0]:.3f} -> {hs[-1]:.3f} over {len(hs)} batches, "
              f"drift {res['schedule']}")
        for ev in res["events"]:
            print(f"[adapt] batch {ev['batch']}: {ev['kind']} "
                  f"(gain {ev.get('gain', 'n/a')})")
        if not res["events"]:
            print("[adapt] no re-plan events (policy held)")
        record = {k: v for k, v in res.items()
                  if k not in _RECORD_DROP and k != "hit_series"}
        record["hit_first"], record["hit_last"] = hs[0], hs[-1]
        if args.json:
            with open(args.json, "w") as f:
                json.dump([record], f, indent=1)
            print(f"# wrote adaptive record to {args.json}")
        if args.metrics_json:
            snap = obs.snapshot().to_json()
            snap["config"] = cfg.name
            snap["adaptive"] = record
            with open(args.metrics_json, "w") as f:
                json.dump(snap, f, indent=1)
            print(f"# wrote metric registry to {args.metrics_json}")
        if args.trace_out:
            obs.tracer().write(
                args.trace_out,
                metadata={"config": cfg.name, "modes": ["adaptive"]},
            )
            print(f"# wrote Chrome trace to {args.trace_out}")
        return 0

    modes = ["sequential", "overlap"] if args.mode == "both" else [args.mode]
    records = []
    for mode in modes:
        res = run_pipeline(
            cfg, batch=batch, batches=args.batches, alpha=args.alpha,
            shards=args.shards, seed=args.seed, mode=mode,
            state=state, params=params, fence=fence,
            profile_dir=args.profile_dir,
        )
        tr = res["traffic"]
        ici = plan.ici_bytes_per_batch(batch, cfg.dim)
        print(
            f"[{mode}] served {res['served']} requests in {res['wall_s']:.2f}s "
            f"-> {res['qps']:.1f} QPS (steady state; compile/warm-up "
            f"{res['compile_s']:.2f}s excluded)"
        )
        print(
            f"[{mode}] batch latency p50={res['lat_p50_s'] * 1e3:.2f}ms "
            f"p95={res['lat_p95_s'] * 1e3:.2f}ms "
            f"p99={res['lat_p99_s'] * 1e3:.2f}ms over {len(res['latencies_s'])} "
            f"batches"
        )
        print(
            f"[{mode}] cache hit rate {res['hit_rate']:.3f}, "
            f"staged {res['staged_per_batch']:.1f} rows/batch, "
            f"HBM {tr['hbm_cached_bytes']}B vs baseline "
            f"{tr['hbm_baseline_bytes']}B ({tr['hbm_reduction']:.2f}x)"
        )
        print(
            f"modeled combine traffic/batch: baseline {ici['baseline']:.0f} B -> "
            f"{ici['duplicated']:.0f} B (saved {ici['saved']:.0f} B)"
        )
        print("first logits:", np.asarray(res["logits"][-1][:4]).round(4).tolist())
        records.append({k: v for k, v in res.items() if k not in _RECORD_DROP})

    # -- observatory epilogue: SLO verdict, attribution, serving report -------
    if slo_engine is not None:
        floors = slo_engine.finalize(hit_rate=res["hit_rate"], qps=res["qps"])
        verdict = "BREACHED" if slo_engine.breached else "met"
        print(
            f"[slo] {slo_engine.spec.name}: {verdict} — "
            f"{slo_engine.bad_total}/{slo_engine.n} bad batches, "
            f"budget remaining {slo_engine.budget_remaining_frac * 100:.1f}%, "
            f"{len(slo_engine.alerts)} alerts"
        )
        for fname, f in floors.items():
            print(f"[slo] {fname} floor {f['floor']}: measured "
                  f"{f['measured']:.3f} — "
                  f"{'BREACHED' if f['breached'] else 'met'}")
    if recorder is not None and recorder.dumps:
        for d in recorder.dumps:
            print(f"[flight] dumped {d['records']} records "
                  f"({d['reason']}) -> {d.get('path', '<memory>')}")
    if args.report:
        att = obs_attribution.attribute(
            obs.tracer().events, res["traffic_report"], state.eplan,
            batch=batch, fenced=fence,
        )
        print(f"[attribution] bottleneck stage: {att.bottleneck} "
              f"(measured {att.total_s * 1e3:.2f} ms/batch, "
              f"cost model {att.modeled_total_s() * 1e3:.2f} ms/batch)")
        rep = obs_report.build(
            snapshot=obs.snapshot(),
            slo_state=slo_engine.state() if slo_engine is not None else None,
            attribution=att,
            traffic=res["traffic"],
            results={r["mode"]: r for r in records},
            flight_dumps=recorder.dumps if recorder is not None else None,
            meta={
                "config": cfg.name, "batch": batch, "batches": args.batches,
                "shards": args.shards, "alpha": args.alpha,
                "seed": args.seed, "modes": modes, "fenced": fence,
            },
        )
        md_path, jpath = obs_report.write(rep, args.report, attribution=att)
        print(f"# wrote serving report to {md_path} (+ {jpath})")

    if args.json:
        with open(args.json, "w") as f:
            json.dump(records, f, indent=1)
        print(f"# wrote {len(records)} records to {args.json}")
    if args.metrics_json:
        snap = obs.snapshot().to_json()
        snap["config"] = cfg.name
        snap["modes"] = {r["mode"]: r for r in records}
        snap["plan"] = state.engine.summary()
        with open(args.metrics_json, "w") as f:
            json.dump(snap, f, indent=1)
        print(f"# wrote metric registry to {args.metrics_json}")
    if args.trace_out:
        obs.tracer().write(
            args.trace_out,
            metadata={"config": cfg.name, "modes": modes, "fenced": fence},
        )
        print(f"# wrote Chrome trace to {args.trace_out} "
              f"(load in chrome://tracing or ui.perfetto.dev)")
    if args.profile_dir:
        print(f"# wrote the profile under {args.profile_dir} (load in "
              f"TensorBoard or ui.perfetto.dev)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
