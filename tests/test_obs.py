"""Telemetry subsystem (repro.obs): histogram math vs numpy, span nesting +
Chrome-trace schema, snapshot merging, drift triggering, the disabled path's
zero-allocation guarantee, and traffic-accounting consistency with the cache
subsystem's own counters."""

import json

import numpy as np
import pytest

from repro import obs
from repro.obs import metrics as M
from repro.obs import traffic as T
from repro.obs.drift import DriftMonitor, rank_agreement
from repro.obs.tracer import Tracer


@pytest.fixture(autouse=True)
def _obs_off():
    """Every test starts and ends with telemetry disabled and wiped."""
    obs.disable()
    obs.registry().reset()
    obs.tracer().reset()
    obs.install_observatory()              # clear any installed observatory
    yield
    obs.disable()
    obs.registry().reset()
    obs.tracer().reset()
    obs.install_observatory()


# ---------------------------------------------------------------------------
# histograms: exact percentiles, bucket math, merging
# ---------------------------------------------------------------------------

def test_histogram_percentiles_match_numpy():
    rng = np.random.default_rng(0)
    samples = rng.lognormal(mean=-6.0, sigma=1.5, size=500)   # us..ms latencies
    h = M.Histogram("lat")
    for s in samples:
        h.record(s)
    for q in (50, 90, 95, 99, 99.9):
        assert h.percentile(q) == pytest.approx(np.percentile(samples, q))
    snap = h.snapshot()
    assert snap.count == 500
    for q in (50, 95, 99):
        assert snap.percentile(q) == pytest.approx(np.percentile(samples, q))


def test_bucketize_edges_and_clipping():
    bounds = M.log_bounds(1e-3, 1e0, per_decade=1)            # 1ms,10ms,0.1s,1s
    counts = M.bucketize(np.array([1e-6, 1e-3, 5e-3, 0.5, 1e3]), bounds)
    assert counts.tolist() == [3, 0, 2]    # under/overflow clip to edge buckets
    assert counts.sum() == 5


def test_bucket_percentile_interpolation_bounds():
    """After a lossy merge the interpolated quantile must land inside the
    bucket that holds the true quantile."""
    rng = np.random.default_rng(1)
    samples = rng.lognormal(mean=-4.0, sigma=1.0, size=2000)
    h = M.Histogram("lat")
    for s in samples:
        h.record(s)
    lossy = h.snapshot().merge(h.snapshot(), drop_samples=True)
    assert lossy.samples.size == 0 and lossy.count == 4000
    for q in (50, 95, 99):
        exact = np.percentile(samples, q)
        est = lossy.percentile(q)         # falls back to bucket interpolation
        b = np.searchsorted(lossy.bounds, exact, side="right") - 1
        assert lossy.bounds[b] <= est <= lossy.bounds[b + 1]


def test_histogram_merge_keeps_exactness_and_rejects_mismatch():
    h1, h2 = M.Histogram("lat"), M.Histogram("lat")
    for v in (0.001, 0.002, 0.003):
        h1.record(v)
    for v in (0.004, 0.005):
        h2.record(v)
    merged = h1.snapshot().merge(h2.snapshot())
    assert merged.count == 5
    assert merged.percentile(50) == pytest.approx(
        np.percentile([0.001, 0.002, 0.003, 0.004, 0.005], 50))
    other = M.Histogram("lat", bounds=M.log_bounds(1e-3, 1e0, per_decade=2))
    with pytest.raises(ValueError, match="different buckets"):
        h1.snapshot().merge(other.snapshot())


def test_registry_snapshot_merge_and_json():
    r1, r2 = M.MetricRegistry(), M.MetricRegistry()
    r1.counter("a").inc(3)
    r1.counter("b").inc(1)
    r2.counter("a").inc(4)
    r1.histogram("h").record(0.01)
    r2.histogram("h").record(0.02)
    r1.attach("plan", {"backend": "packed"})
    merged = r1.snapshot().merge(r2.snapshot())
    assert merged.counters == {"a": 7, "b": 1}
    assert merged.histograms["h"].count == 2
    j = merged.to_json()
    json.dumps(j)                          # JSON-serializable end to end
    assert j["info"]["plan"]["backend"] == "packed"
    assert j["histograms"]["h"]["count"] == 2
    assert j["histograms"]["h"]["p50"] <= j["histograms"]["h"]["p99"]


# ---------------------------------------------------------------------------
# tracer: nesting + Chrome-trace schema
# ---------------------------------------------------------------------------

def test_span_nesting_and_chrome_schema():
    tr = Tracer()
    with tr.span("outer"):
        with tr.span("inner", args={"batch": 1}):
            pass
        with tr.span("inner2"):
            pass
    tr.instant("marker")
    tr.counter("hit_rate", {"v": 0.5})
    doc = tr.to_chrome(metadata={"run": "test"})
    json.dumps(doc)                        # valid JSON document
    assert doc["displayTimeUnit"] == "ms"
    assert doc["otherData"] == {"run": "test"}
    ev = doc["traceEvents"]
    assert ev[0]["ph"] == "M"              # process_name metadata first
    spans = {e["name"]: e for e in ev if e["ph"] == "X"}
    assert set(spans) == {"outer", "inner", "inner2"}
    for e in spans.values():               # required complete-event fields
        assert {"name", "cat", "ph", "ts", "dur", "pid", "tid"} <= set(e)
        assert e["ts"] >= 0 and e["dur"] >= 0
    # nesting: children contained in the parent interval, depth recorded
    outer, inner = spans["outer"], spans["inner"]
    assert outer["args"]["depth"] == 0 and inner["args"]["depth"] == 1
    assert inner["ts"] >= outer["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    assert inner["args"]["batch"] == 1
    assert any(e["ph"] == "i" for e in ev) and any(e["ph"] == "C" for e in ev)


def test_tracer_write_roundtrip(tmp_path):
    tr = Tracer()
    with tr.span("s"):
        pass
    path = tmp_path / "trace.json"
    tr.write(str(path))
    doc = json.load(open(path))
    assert any(e["ph"] == "X" and e["name"] == "s" for e in doc["traceEvents"])


# ---------------------------------------------------------------------------
# drift monitor
# ---------------------------------------------------------------------------

def test_rank_agreement_noise_floor():
    perfect = [(1.0, 1.0), (2.0, 2.1), (3.0, 3.3)]
    a, n = rank_agreement(perfect)
    assert a == 1.0 and n == 3
    inverted = [(1.0, 3.0), (2.0, 2.0), (3.0, 1.0)]
    a, n = rank_agreement(inverted)
    assert a == 0.0 and n == 3
    tied = [(1.0, 1.0), (2.0, 1.01)]       # measured gap under the 10% floor
    a, n = rank_agreement(tied)
    assert a == 1.0 and n == 0


def test_drift_monitor_triggers_on_residual_step():
    mon = DriftMonitor(window=8, rel_tol=0.25, min_points=8)
    for _ in range(8):                     # calibration: model ~10% under
        mon.observe(0.010, 0.011)
    assert not mon.refit_recommended
    assert mon.drift == pytest.approx(0.0)
    for _ in range(8):                     # regime shift: measured 2x predicted
        mon.observe(0.010, 0.020)
    assert mon.drift > 0.25
    assert mon.refit_recommended
    s = mon.summary()
    assert s["refit_recommended"] and s["observations"] == 16
    json.dumps(s)


def test_drift_monitor_tolerates_constant_bias():
    """A uniformly 2x-off model ranks knobs fine — no refit trigger."""
    mon = DriftMonitor(window=8, min_points=8)
    rng = np.random.default_rng(2)
    for _ in range(24):
        p = rng.uniform(0.005, 0.05)
        mon.observe(p, 2.0 * p)            # constant multiplicative bias
    assert not mon.refit_recommended
    agreement, counted = mon.recent_rank_agreement()
    assert counted > 0 and agreement == 1.0


# ---------------------------------------------------------------------------
# enable/disable switch: the disabled path records nothing and allocates
# no per-call objects
# ---------------------------------------------------------------------------

def test_disabled_mode_records_nothing():
    assert not obs.enabled()
    obs.inc("x")
    obs.observe("h", 0.01)
    obs.set_gauge("g", 1.0)
    obs.attach("k", {"v": 1})
    with obs.span("s"):
        pass
    obs.instant("i")
    obs.trace_counter("c", v=1.0)
    assert obs.observe_batch(batch=0, mode="overlap", latency_s=0.01) is None
    snap = obs.snapshot()
    assert snap.counters == {} and snap.histograms == {} and snap.info == {}
    assert obs.tracer().events == []


def test_disabled_observe_batch_bypasses_installed_observatory():
    """Even with an observatory installed, the disabled facade is one bool
    check — the SLO engine and flight recorder see nothing."""
    eng = obs.SLOEngine(obs.SLOSpec(p99_latency_s=1e-9, fast_window=1,
                                    slow_window=1))
    rec = obs.FlightRecorder(capacity=4, min_history=1)
    obs.install_observatory(slo=eng, recorder=rec)
    assert not obs.enabled()
    assert obs.observe_batch(batch=0, mode="overlap", latency_s=99.0) is None
    assert eng.n == 0 and len(rec) == 0 and rec.dumps == []


def test_disabled_span_is_shared_singleton():
    # allocation-free: every disabled span() call returns the same object
    s1 = obs.span("a", batch=1)
    s2 = obs.span("b", mode="overlap")
    assert s1 is s2 is obs.NULL_SPAN


def _profiled(tmp_path, body):
    """Host-plane events of a ``jax.profiler`` capture around ``body()``, as
    ``{name: [stats dict, ...]}``."""
    import glob

    import jax
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        body()
    path, = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    out.setdefault(e.name, []).append(dict(e.stats))
    return out


def test_enabled_span_is_on_the_profilers_host_plane(tmp_path):
    obs.enable()

    def body():
        with obs.span("x", batch=3):
            with obs.span("batch", batch=4, mode="sequential") as sp:
                sp.set(staged=7)

    events = _profiled(tmp_path, body)
    assert events["x"] == [{"batch": 3}]
    step, = events["batch"]
    assert step["step_num"] == 4 and step["staged"] == 7
    assert step["mode"] == "sequential"
    got = {e["name"]: e["args"] for e in obs.tracer().events}
    assert got["batch"]["staged"] == 7 and got["x"]["batch"] == 3


def test_disabled_span_reaches_no_profile(tmp_path):
    def body():
        with obs.span("x", batch=3) as sp:
            assert sp is obs.NULL_SPAN
            sp.set(staged=1)

    assert "x" not in _profiled(tmp_path, body)
    assert obs.tracer().events == []


def test_enable_records_and_reset_wipes():
    obs.enable()
    obs.inc("x", 2)
    obs.observe("h", 0.01)
    with obs.span("s"):
        pass
    snap = obs.snapshot()
    assert snap.counters["x"] == 2 and snap.histograms["h"].count == 1
    assert any(e["name"] == "s" for e in obs.tracer().events)
    obs.enable(reset=True)                 # re-enable wipes prior state
    assert obs.snapshot().counters == {} and obs.tracer().events == []


# ---------------------------------------------------------------------------
# traffic accounting: must agree with CacheStats and cache_sim on one trace
# ---------------------------------------------------------------------------

def _zipf_rows(vocab, batches, batch, pooling, seed=3):
    from repro.data.synthetic import zipf_trace

    n = batches * batch * pooling
    return zipf_trace(vocab, n, alpha=1.05, seed=seed).reshape(
        batches, batch * pooling)


def test_cache_traffic_matches_cachestats():
    from repro.cache.sram_cache import PrefetchScheduler

    rows = _zipf_rows(4096, 6, 32, 8)
    sched = PrefetchScheduler(4096, 128)
    for t in range(rows.shape[0]):
        sched.prefetch(rows[t])
        sched.slots_for(rows[t], record=True)
    stats = sched.stats
    tr = T.cache_traffic(stats, row_bytes=512)
    assert tr["accesses"] == stats.accesses
    assert tr["hits"] == stats.hits
    assert tr["misses"] == stats.accesses - stats.hits
    assert tr["hit_rate"] == pytest.approx(stats.hit_rate)
    assert tr["staged_rows"] == stats.staged_rows
    # priced exactly like CacheStats' own model
    tb = stats.traffic_bytes(512)
    assert tr["hbm_baseline_bytes"] == tb["baseline"]
    assert tr["hbm_cached_bytes"] == tb["cached"]
    assert 0.0 <= tr["hit_rate"] <= 1.0
    assert tr["hbm_cached_bytes"] <= tr["hbm_baseline_bytes"]
    assert "hit=" in T.format_cache_traffic(tr)


def test_cache_traffic_agrees_with_cache_sim(capsys):
    """The benchmark's reported hit rate and the traffic module's must be the
    same number on the same trace (same scheduler, same slots)."""
    from benchmarks.cache_sim import qr_cache_sweep
    from repro.cache import intra_gnr
    from repro.cache.sram_cache import simulate
    from repro.core.qr_embedding import EmbeddingConfig

    kw = dict(vocab=16_384, collision=16, pooling=8, batch=64, n_batches=6)
    bench_hit = qr_cache_sweep(slot_sweep=(64, 128), default_slots=128, **kw)
    capsys.readouterr()                    # swallow the emitted rows
    # replay: same trace construction as benchmarks.cache_sim._batches
    trace = _zipf_rows(kw["vocab"], kw["n_batches"], kw["batch"], kw["pooling"])
    cfg = EmbeddingConfig(vocab=kw["vocab"], dim=128, kind="qr",
                          collision=kw["collision"])
    q, q_rows, row_bytes = intra_gnr.subtable_traces(trace, cfg)["q"]
    stats = simulate([q[t] for t in range(kw["n_batches"])], q_rows, 128)
    tr = T.cache_traffic(stats, row_bytes)
    assert tr["hit_rate"] == pytest.approx(bench_hit)


def test_traffic_report_from_serving_pipeline():
    """End-to-end: run_pipeline's summed hit rate equals the TrafficReport's,
    and the report carries the duplication plan's comm model."""
    from repro.configs import registry
    from repro.launch import serve_rec

    cfg = registry.get_dlrm("dlrm-qr-smoke")
    obs.enable()
    res = serve_rec.run_pipeline(cfg, batch=4, batches=3, mode="sequential")
    tr = res["traffic"]
    assert tr["hit_rate"] == pytest.approx(res["hit_rate"])
    assert tr["accesses"] == sum(t["accesses"] for t in tr["per_table"])
    assert len(tr["per_table"]) == cfg.num_tables
    assert tr["hbm_cached_bytes"] <= tr["hbm_baseline_bytes"]
    assert tr["comm_saved_bytes_per_batch"] >= 0.0
    # latency distribution replaced the single wall number
    assert res["compile_s"] > 0 and len(res["latencies_s"]) == 2
    assert res["lat_p50_s"] <= res["lat_p95_s"] <= res["lat_p99_s"]
    # telemetry side: histograms + engine dispatch counters + spans landed
    snap = obs.snapshot()
    assert snap.histograms["serve/sequential/batch_latency_s"].count == 2
    assert snap.counters["engine/dispatch/serve_gather"] >= 3
    names = {e["name"] for e in obs.tracer().events}
    assert {"prefetch", "pack", "h2d", "dispatch", "interact"} <= names
