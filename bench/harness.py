"""One run of one cell of ``BENCHMARK.json``.

A cell names a configuration (``configs/<name>.json``) and a traffic mix
(``traffic/<name>.json``); each metric is read by ``metrics/<name>.py``, or
by ``metrics/<stem>.py`` for a metric ``<stem>.<suffix>`` split by what it
moves, and each cell's correctness limits are ``limits/<cell>.json``.  The
harness finds all of them by name, so a new cell, mix or metric is a new
file.

A run:
1. set-up: the weights from the seed (one jitted call), the program's
   offline plan (``serve_rec.build_serve_state``), and a short
   ``run_pipeline`` call that compiles every shape and times a batch;
2. the window: one ``run_pipeline`` call with as many batches as fill
   ``--seconds``; its batches come from the benchmark's generator, put in
   place of ``repro.data.synthetic.dlrm_batch`` for the run;
3. the check: the window's logits, and the pooled embeddings that the
   timed gather fed to the head for a sample of its batches, against the
   plain float32 reference (``reference/dlrm.py``), run after the peak
   memory has been read;
4. the metrics: with ``trace=False`` the cell's end-to-end metrics, with
   ``trace=True`` its per-layer ones, read from a profiler trace of the
   window and the program's ``obs`` spans and counters.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import importlib.util
import json
import math
import sys
import tempfile
import time
from pathlib import Path

import jax
import numpy as np

from bench import counts
from bench import model as model_mod
from bench import peaks as peaks_mod
from bench import trace as trace_mod
from bench.reference import dlrm as reference
from bench.traffic import generator

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
COMPILE_COUNTER = "engine/compile/serve_gather"
POOLED_SAMPLE = 8       # window batches whose pooled embeddings are compared
JAX_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass(frozen=True)
class Dirs:
    """Where the harness looks up each kind of file by name."""

    configs: Path = BENCH / "configs"
    traffic: Path = BENCH / "traffic"
    metrics: Path = BENCH / "metrics"
    limits: Path = BENCH / "limits"


def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r}; known: "
                   f"{[c['name'] for c in spec['workloads']]}")


def cell_metrics(spec: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: its end-to-end ones, or with
    ``trace`` its per-layer ones."""
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def reader_path(metrics: Path, name: str) -> Path:
    """``<name>.py``, else the reader of the metric's stem, ``<stem>.py``."""
    path = metrics / f"{name}.py"
    return path if path.is_file() else metrics / f"{name.split('.')[0]}.py"


@functools.cache
def reader(path: Path):
    """The ``read(ctx)`` function of a metric's reader file."""
    mod_name = "bench_metric_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class _PooledTap:
    """Stands in for ``serve_rec._head_jit`` during the window call: passes
    every call on, and keeps the pooled embeddings of the calls in ``keep``
    as the device arrays they are, so the window does not sync on them."""

    def __init__(self, head, keep: set[int]):
        self.head, self.keep = head, keep
        self.calls, self.kept = 0, {}

    def __call__(self, params, dense, pooled, cfg):
        if self.calls in self.keep:
            self.kept[self.calls] = pooled
        self.calls += 1
        return self.head(params, dense, pooled, cfg)


def pooled_sample(seed: int, batches: int) -> list[int]:
    """The window batches, drawn from the seed, whose pooled embeddings the
    check compares."""
    rng = np.random.default_rng(int(seed))
    return sorted(rng.choice(batches, min(POOLED_SAMPLE, batches),
                             replace=False).tolist())


class _CompileCount:
    """Backend compiles seen by JAX's monitoring, for the whole process."""

    n = 0
    listening = False

    @classmethod
    def listen(cls, event, *_args, **_kw):
        if event == JAX_COMPILE_EVENT:
            cls.n += 1


@dataclasses.dataclass
class Context:
    """What a metric reader may read about one run."""

    model: model_mod.Model
    result: dict            # run_pipeline's window record, logits dropped
    window: list[dict]      # the window's generated batches
    setup_s: float
    spans: list[list]       # obs spans on the trace clock (trace runs)
    trace: dict | None      # trace_mod.summarize (trace runs on a TPU)
    peaks: dict | None

    @property
    def window_batches(self) -> int:
        return len(self.window)

    @functools.cached_property
    def window_ids(self) -> list[np.ndarray]:
        return [np.asarray(b["idx"]) for b in self.window]

    def span_ms_per_batch(self, names) -> float | None:
        """Host milliseconds per window batch in the named ``obs`` spans."""
        hit = [s for s in self.spans
               if s[0] in names and s[3].get("batch", -1) >= 1]
        if not hit:
            return None
        return sum(s[2] for s in hit) * 1e-6 / self.window_batches

    def module_s(self, pattern: str) -> tuple[float, int] | None:
        """Device seconds and runs of the named XLA module in the window."""
        if self.trace is None:
            return None
        ns, count = trace_mod.module_ns(self.trace["modules"], pattern,
                                        self.trace["lo"], self.trace["hi"])
        return (ns * 1e-9, count) if count else None

    def module_ms_per_batch(self, pattern: str) -> float | None:
        """Device milliseconds per run of the named XLA module in the window
        (one run a batch)."""
        got = self.module_s(pattern)
        return None if got is None else got[0] * 1e3 / got[1]

    @functools.cached_property
    def _gather_counts(self) -> list[dict]:
        return [counts.gather_counts(ids, self.model) for ids in self.window_ids]

    def least_s(self, part: str) -> float | None:
        """Summed roofline least time of the window's batches for ``part``
        (``gather`` or ``step``), from ``counts``; None off a chip with
        published peaks."""
        if self.peaks is None:
            return None
        total = 0.0
        for ids, g in zip(self.window_ids, self._gather_counts):
            c = g if part == "gather" else counts.step_counts(
                g, counts.head_counts(ids.shape[0], self.model))
            total += peaks_mod.least_time_s(c["flops"], c["bytes"], self.peaks)[0]
        return total


@dataclasses.dataclass
class Cell:
    """A cell's files, resolved, and the program's registry config."""

    name: str
    spec: dict
    dirs: Dirs
    model: model_mod.Model
    mix: dict
    limits: dict
    cfg: object
    devices: list


def prepare(workload: str, *, spec: dict | None = None, dirs: Dirs = Dirs(),
            require_tpu: bool = True) -> Cell:
    """Resolve ``workload``'s files; raise ``NoChip`` unless JAX finds the
    TPU chips the cell asks for (``require_tpu=False`` is for CPU tests)."""
    spec = load_spec() if spec is None else spec
    cell = find_cell(spec, workload)
    m = model_mod.load(cell["config"], dirs.configs)
    mix = generator.load(cell["traffic"], dirs.traffic)
    limits = json.loads((dirs.limits / f"{workload}.json").read_text())
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < cell["chips"]):
        raise NoChip(f"cell {workload} needs {cell['chips']} TPU chip(s); "
                     f"JAX finds {len(devices)} {devices[0].platform} device(s)")

    from repro.configs import registry

    if not _CompileCount.listening:
        jax.monitoring.register_event_duration_secs_listener(_CompileCount.listen)
        _CompileCount.listening = True
    cfg = registry.get_dlrm(m.registry_id)
    model_mod.check_program_config(m, cfg)
    return Cell(workload, spec, dirs, m, mix, limits, cfg, devices)


@dataclasses.dataclass
class Served:
    """What the window served, with the batches it was given."""

    params: dict
    window: list[dict]          # generated batches of the window
    logits: list[np.ndarray]    # the program's logits for them
    pooled: dict[int, np.ndarray]   # window batch -> pooled embeddings fed
                                    # to the head, for the sampled batches
    result: dict                # run_pipeline's record, logits dropped
    setup_s: float
    outer_s: float
    compiles: int               # serve_gather traces in the window call
    xla_compiles: int           # XLA backend compiles in the window call
    peak_bytes: int | None
    spans: list[list]
    trace: dict | None


def serve(c: Cell, seed: int, seconds: float, trace: bool, *,
          process_start: float) -> Served:
    """Set up from ``seed`` and serve one window of ``seconds`` through
    ``run_pipeline``, with the benchmark's generator in the seam and its tap
    on the head's pooled input."""
    from repro import obs
    from repro.data import synthetic
    from repro.launch import serve_rec

    mix = c.mix
    telemetry_was_on = obs.enabled()
    obs.enable()
    seam = generator.Seam(mix, seed)
    program_batch = synthetic.dlrm_batch
    synthetic.dlrm_batch = seam
    try:
        params = jax.block_until_ready(model_mod.make_params(c.model, seed))
        state = serve_rec.build_serve_state(c.cfg, shards=c.model.plan_shards,
                                            alpha=mix["alpha"], seed=seed)
        kw = dict(batch=mix["batch"], alpha=mix["alpha"], seed=seed,
                  mode=mix["mode"], state=state, params=params, fence=False)
        warm_n = mix["warmup_batches"]
        warm = serve_rec.run_pipeline(c.cfg, batches=warm_n, **kw)
        _check_drawn(seam.take(), warm_n, "warm-up")
        n = max(2, round(seconds * (warm_n - 1) / warm["wall_s"])) + 1
        del warm
        counter = obs.registry().counter(COMPILE_COUNTER)
        compiles0, xla0 = counter.value, _CompileCount.n
        first_event = len(obs.tracer().events)     # spans of the window call
        # call t of the head is batch t; window batch j is batch j + 1
        tap = _PooledTap(serve_rec._head_jit,
                         {j + 1 for j in pooled_sample(seed, n - 1)})
        serve_rec._head_jit = tap
        with tempfile.TemporaryDirectory() as tdir:
            profiler = (jax.profiler.trace(tdir, profiler_options=_profile_options())
                        if trace else contextlib.nullcontext())
            try:
                with profiler:
                    with jax.profiler.TraceAnnotation(trace_mod.SYNC):
                        sync_pc = time.perf_counter()
                    t_call = time.perf_counter()
                    res = serve_rec.run_pipeline(c.cfg, batches=n, **kw)
                    outer_s = time.perf_counter() - t_call
                    setup_s = time.time() - process_start - res["wall_s"]
            finally:
                serve_rec._head_jit = tap.head
            if trace:
                tr = trace_mod.extract(tdir)
        spans, summary = [], None
        if trace:
            spans = trace_mod.spans_on_trace_clock(
                obs.tracer().events[first_event:], obs.tracer().origin, sync_pc,
                trace_mod.sync_ns(tr))
            summary = trace_mod.summarize(tr, spans)
        compiles = counter.value - compiles0
    finally:
        synthetic.dlrm_batch = program_batch
        if not telemetry_was_on:        # leave the process as it was found
            obs.disable()
            obs.registry().reset()
            obs.tracer().reset()
    drawn = seam.take()
    _check_drawn(drawn, n, "window")
    if tap.calls != n:
        raise RuntimeError(
            f"window: the program called serve_rec._head_jit {tap.calls} "
            f"times, not {n}; the check no longer sees its pooled embeddings")
    peak = _peak_bytes(c.devices)
    pooled = {t - 1: np.asarray(x) for t, x in tap.kept.items()}
    del tap
    logits = [np.asarray(x) for x in res.pop("logits")[1:]]
    res.pop("traffic_report", None)
    del state, kw
    gc.collect()
    # batch 0 of the call compiles (in-process cache hit here) and is untimed
    return Served(params=params, window=drawn[1:], logits=logits,
                  pooled=pooled, result=res,
                  setup_s=setup_s, outer_s=outer_s,
                  compiles=compiles,
                  xla_compiles=_CompileCount.n - xla0, peak_bytes=peak,
                  spans=spans, trace=summary)


def run_cell(c: Cell, seed: int, seconds: float, trace: bool, *,
             process_start: float | None = None,
             log=lambda msg: print(msg, file=sys.stderr, flush=True)) -> dict:
    """Run the prepared cell ``c`` once and return its result line."""
    process_start = time.time() if process_start is None else process_start
    workload = c.name
    s = serve(c, seed, seconds, trace, process_start=process_start)
    res, mix = s.result, c.mix
    n = len(s.window)
    log(f"[{workload}] window: {n} batches of {mix['batch']} ({mix['mode']}), "
        f"outer clock {s.outer_s:.6f} s, wall_s {res['wall_s']:.6f} s, "
        f"set-up {s.setup_s:.6f} s")
    log(f"[{workload}] compiles in the window call: serve_gather trace "
        f"counter {s.compiles}, XLA backend compiles {s.xla_compiles}; "
        f"peak_bytes_in_use {s.peak_bytes}")
    problems = []
    if s.outer_s < res["wall_s"]:
        problems.append(f"outer clock {s.outer_s} s < wall_s {res['wall_s']} s")
    if res["served"] != mix["batch"] * n:
        problems.append(f"served {res['served']} != {mix['batch']} x {n}")

    lat_ms = np.asarray(res["latencies_s"]) * 1e3
    log(f"[{workload}] batch latency ms p50 {np.percentile(lat_ms, 50):.3f} "
        f"p95 {np.percentile(lat_ms, 95):.3f} max {lat_ms.max():.3f} over "
        f"{lat_ms.size} batches ({mix['mode']}: cycle times in overlap mode)")
    t_check = time.perf_counter()
    checks, failed = compare(s.params, s.window, s.logits, s.pooled, c.model,
                             c.limits)
    log(f"[{workload}] reference over {n} batches in "
        f"{time.perf_counter() - t_check:.3f} s")
    s.params = None
    correct = not problems and all(v["value"] <= v["limit"] for v in checks.values())
    dev = c.devices[0]
    ctx = Context(model=c.model, result=res, window=s.window,
                  setup_s=s.setup_s, spans=s.spans, trace=s.trace,
                  peaks=peaks_mod.peaks(dev.device_kind)
                  if dev.platform == "tpu" else None)
    metrics = {}
    for metric in cell_metrics(c.spec, workload, trace):
        value = reader(reader_path(c.dirs.metrics, metric["name"]))(ctx)
        if value is not None:
            metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(c.devices), "memory_peak_bytes": s.peak_bytes}
    line = {"correct": correct, "attempted": res["served"], "failed": failed,
            "metrics": metrics, "device": device}
    if s.trace is not None:
        device["busy_s"] = s.trace["busy_s"]
        device["window_s"] = s.trace["window_s"]
        line["breakdown"] = s.trace["breakdown"]
    for p in problems:
        log(f"[{workload}] FAILED: {p}")
    for name, v in checks.items():
        log(f"[{workload}] check {name} = {v['value']!r} (limit {v['limit']!r})")
    line["checks"] = checks
    return line


def compare(params, window, logits, pooled, m, limits):
    """The program's answers against the float32 reference.

    ``logit_max_abs_err``: the widest logit gap over every window sample.
    ``pooled_max_rel_err``: the widest gap of a pooled embedding entry, over
    the sampled batches in ``pooled``, as a share of the largest entry of
    the reference's pooled embeddings in that batch.  Returns both beside
    their limits, and the samples whose logit or pooled entries pass a
    limit or are not finite."""
    logit_gap, pooled_gap, failed = 0.0, 0.0, 0
    lim_logit = limits["logit_max_abs_err"]
    lim_pooled = limits["pooled_max_rel_err"]
    for j, (batch, got) in enumerate(zip(window, logits)):
        want, want_pooled = reference.forward_with_pooled(
            params, batch["dense"], batch["idx"], m)
        err = np.abs(got.astype(np.float64) - np.asarray(want))
        bad = ~(err <= lim_logit)                  # NaN and inf fail too
        logit_gap = max(logit_gap, _widest(err))
        if j in pooled:
            ref = np.asarray(want_pooled, np.float64)
            rel = np.abs(pooled[j].astype(np.float64) - ref) / np.abs(ref).max()
            rel = rel.reshape(rel.shape[0], -1)
            bad |= ~(rel <= lim_pooled).all(axis=1)
            pooled_gap = max(pooled_gap, _widest(rel))
        failed += int(bad.sum())
    return {"logit_max_abs_err": {"value": logit_gap, "limit": lim_logit},
            "pooled_max_rel_err": {"value": pooled_gap, "limit": lim_pooled}}, failed


def _widest(err: np.ndarray) -> float:
    return float(err.max()) if np.isfinite(err).all() else math.inf


def _check_drawn(drawn, want: int, what: str) -> None:
    if len(drawn) != want:
        raise RuntimeError(
            f"{what}: the program drew {len(drawn)} batches from the "
            f"benchmark's generator, not {want}; it no longer serves the "
            "benchmark's traffic")


def _peak_bytes(devices) -> int | None:
    """Peak bytes in use on the fullest device, where the backend says."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    return max((p for p in peaks if p is not None), default=None)


def _profile_options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # a Python tracer would slow the host loop
    opts.host_tracer_level = 2
    return opts
