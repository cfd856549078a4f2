"""Observatory layer (repro.obs.{slo,recorder,attribution,report}): burn-rate
window math against hand-computed budgets, flight-recorder ring bounding and
dump-on-breach, attribution-vs-cost-model consistency, the serving-report
artifact, and the end-to-end serve_rec wiring (flight dumps whose records
match the tracer's span durations)."""

import json

import numpy as np
import pytest

from repro import obs
from repro.obs import attribution as A
from repro.obs import report as R
from repro.obs.recorder import BatchRecord, FlightRecorder, TelemetryJoin
from repro.obs.slo import SLOEngine, SLOSpec


@pytest.fixture(autouse=True)
def _obs_off():
    obs.disable()
    obs.registry().reset()
    obs.tracer().reset()
    obs.install_observatory()
    yield
    obs.disable()
    obs.registry().reset()
    obs.tracer().reset()
    obs.install_observatory()


# ---------------------------------------------------------------------------
# SLOSpec: CLI parsing + validation
# ---------------------------------------------------------------------------

def test_slospec_parse_cli_form():
    spec = SLOSpec.parse("p99_ms=50,hit=0.5,qps=100,objective=0.95,"
                         "fast_window=4,slow_window=16,name=prod")
    assert spec.p99_latency_s == pytest.approx(0.050)
    assert spec.hit_rate_floor == 0.5
    assert spec.qps_floor == 100.0
    assert spec.objective == 0.95
    assert spec.fast_window == 4 and spec.slow_window == 16
    assert spec.name == "prod"
    assert spec.budget_fraction == pytest.approx(0.05)
    json.dumps(spec.describe())


def test_slospec_parse_rejects_unknown_keys_and_bad_windows():
    with pytest.raises(ValueError, match="unknown --slo key"):
        SLOSpec.parse("p99ms=50")
    with pytest.raises(ValueError, match="key=value"):
        SLOSpec.parse("p99_ms")
    with pytest.raises(ValueError, match="fast_window"):
        SLOSpec(p99_latency_s=0.05, fast_window=8, slow_window=4)
    with pytest.raises(ValueError, match="objective"):
        SLOSpec(objective=1.0)


# ---------------------------------------------------------------------------
# burn-rate window math vs hand-computed budgets
# ---------------------------------------------------------------------------

def test_burn_rate_hand_computed():
    # objective 0.9 -> 10% budget: a window's burn = bad_fraction / 0.1
    eng = SLOEngine(SLOSpec(p99_latency_s=0.010, objective=0.9,
                            fast_window=4, slow_window=8))
    for _ in range(8):
        eng.observe(0.005)                  # 8 good
    assert eng.burn_rate(4) == 0.0 and eng.burn_rate(8) == 0.0
    for _ in range(2):
        eng.observe(0.020)                  # 2 bad
    # fast window = last 4 = [good, good, bad, bad] -> 0.5 / 0.1 = 5x
    assert eng.burn_rate(4) == pytest.approx(5.0)
    # slow window = last 8 = 6 good 2 bad -> 0.25 / 0.1 = 2.5x
    assert eng.burn_rate(8) == pytest.approx(2.5)
    # budget: 10 observations at 10% -> 1.0 allowed, 2 spent -> blown
    assert eng.budget_allowed == pytest.approx(1.0)
    assert eng.budget_spent == 2
    assert eng.budget_remaining_frac == pytest.approx(1.0 - 2.0 / 1.0)
    assert eng.breached                     # negative budget => breached


def test_burn_rate_short_history_uses_what_exists():
    eng = SLOEngine(SLOSpec(p99_latency_s=0.010, objective=0.9,
                            fast_window=4, slow_window=8))
    eng.observe(0.020)
    # only 1 observation: window of 8 sees [bad] -> 1.0 / 0.1 = 10x
    assert eng.burn_rate(8) == pytest.approx(10.0)


def test_page_alert_needs_both_windows_and_is_edge_triggered():
    # all-bad stream: both windows saturate -> exactly ONE page alert fires
    # (edge-triggered), not one per burning batch
    eng = SLOEngine(SLOSpec(p99_latency_s=0.010, objective=0.99,
                            fast_window=2, slow_window=4,
                            page_burn=10.0))
    fired = []
    for _ in range(10):
        fired += eng.observe(0.020)
    assert [a["severity"] for a in fired] == ["page"]
    assert fired[0]["at_batch"] == 1        # fired as soon as fast_window filled
    assert fired[0]["fast_burn"] == pytest.approx(100.0)
    assert eng.breached


def test_ticket_alert_on_slow_leak():
    # 1-in-3 bad: slow burn ~ 0.33/0.01 = 33x >= ticket(2) but the fast
    # window must NOT page (page needs BOTH windows >= 10 -- here fast often
    # is, so pick a sparser leak against a 10% budget instead)
    eng = SLOEngine(SLOSpec(p99_latency_s=0.010, objective=0.9,
                            fast_window=4, slow_window=12,
                            page_burn=10.0, ticket_burn=2.0))
    fired = []
    # 1 bad in every 4: slow burn = (3/12)/0.1 = 2.5x >= 2, fast burn =
    # (1/4)/0.1 = 2.5x < 10 -> ticket, never page
    for i in range(24):
        fired += eng.observe(0.020 if i % 4 == 0 else 0.005)
    sevs = {a["severity"] for a in fired}
    assert sevs == {"ticket"}


def test_evaluate_snapshot_streams_without_double_count():
    eng = SLOEngine(SLOSpec(p99_latency_s=0.010, objective=0.9,
                            fast_window=2, slow_window=4))
    obs.enable()
    for v in (0.005, 0.020, 0.005):
        obs.observe("serve/overlap/batch_latency_s", v)
    eng.evaluate_snapshot(obs.snapshot())
    assert eng.n == 3 and eng.bad_total == 1
    eng.evaluate_snapshot(obs.snapshot())   # same snapshot: nothing new
    assert eng.n == 3
    obs.observe("serve/overlap/batch_latency_s", 0.030)
    eng.evaluate_snapshot(obs.snapshot())   # only the new sample consumed
    assert eng.n == 4 and eng.bad_total == 2


def test_finalize_floors_and_state_json():
    eng = SLOEngine(SLOSpec(p99_latency_s=0.010, hit_rate_floor=0.8,
                            qps_floor=100.0, objective=0.9))
    eng.observe(0.005)
    floors = eng.finalize(hit_rate=0.95, qps=50.0)
    assert not floors["hit_rate"]["breached"]
    assert floors["qps"]["breached"]
    assert eng.breached                     # the qps floor alone breaches
    state = eng.state()
    json.dumps(state)
    assert state["breached"] and state["floors"]["qps"]["measured"] == 50.0
    assert state["observations"] == 1 and state["bad_events"] == 0


# ---------------------------------------------------------------------------
# flight recorder: ring bounding, MAD anomaly, dump caps
# ---------------------------------------------------------------------------

def _record(batch, lat, **kw):
    return BatchRecord(batch=batch, mode="overlap", latency_s=lat,
                       stages={}, counters={}, **kw)


def test_ring_is_bounded():
    rec = FlightRecorder(capacity=4)
    for t in range(10):
        rec.observe(_record(t, 0.01))
    assert len(rec) == 4
    assert [r.batch for r in rec.records] == [6, 7, 8, 9]


def test_mad_anomaly_threshold_and_dump(tmp_path):
    rec = FlightRecorder(capacity=8, out_dir=str(tmp_path), mad_k=6.0,
                         min_history=8)
    for t in range(8):                      # flat baseline ~10ms
        assert rec.observe(_record(t, 0.010 + 1e-5 * t)) is None
    cut = rec.anomaly_threshold()
    # flat history: MAD ~ 0, the relative floor keeps cut ~ med * 1.3
    assert 0.010 < cut < 0.020
    dump = rec.observe(_record(8, 0.050))   # 5x step: anomalous
    assert dump is not None and dump["reason"] == "latency_anomaly"
    assert rec.records[-1].anomaly
    doc = json.load(open(dump["path"]))
    assert doc["reason"] == "latency_anomaly"
    assert doc["context"]["trigger_batch"] == 8
    assert len(doc["records"]) == 8         # ring snapshot at dump time
    assert doc["records"][-1]["anomaly"]


def test_no_anomaly_before_min_history():
    rec = FlightRecorder(min_history=8)
    for t in range(5):
        rec.observe(_record(t, 0.010))
    assert rec.anomaly_threshold() is None
    assert rec.observe(_record(5, 10.0)) is None   # judged unknowable, kept
    assert not rec.records[-1].anomaly


def test_slo_alert_dump_and_max_dumps_cap(tmp_path):
    rec = FlightRecorder(capacity=4, out_dir=str(tmp_path), max_dumps=2)
    alert = {"severity": "page", "at_batch": 0}
    d0 = rec.observe(_record(0, 0.01), alerts=[alert])
    assert d0["reason"] == "slo_burn:page"
    d1 = rec.observe(_record(1, 0.01), alerts=[alert])
    assert d1 is not None
    assert rec.observe(_record(2, 0.01), alerts=[alert]) is None   # capped
    assert len(rec.dumps) == 2
    assert sorted(p.name for p in tmp_path.iterdir()) == [
        "flight_000.json", "flight_001.json",
    ]


def test_telemetry_join_stages_and_counter_deltas():
    obs.enable()
    join = TelemetryJoin(obs.tracer(), obs.registry())
    with obs.span("prefetch", batch=3):
        pass
    with obs.span("dispatch", batch=3):
        pass
    with obs.span("batch", batch=3):        # wrapper: dropped from stages
        pass
    with obs.span("pack_tables"):           # no batch arg: ignored
        pass
    obs.inc("engine/dispatch/serve_gather")
    r = join.next_record(batch=3, mode="overlap", latency_s=0.01)
    assert set(r.stages) == {"prefetch", "dispatch"}
    assert all(v >= 0.0 for v in r.stages.values())
    assert r.counters == {"engine/dispatch/serve_gather": 1}
    # deltas, not totals: an idle next batch carries no counters
    r2 = join.next_record(batch=4, mode="overlap", latency_s=0.01)
    assert r2.stages == {} and r2.counters == {}


# ---------------------------------------------------------------------------
# attribution: cost-model consistency + bottleneck flagging
# ---------------------------------------------------------------------------

def _serve_session(batches=4, batch=4):
    from repro.configs import registry
    from repro.launch import serve_rec

    cfg = registry.get_dlrm("dlrm-qr-smoke")
    obs.enable()
    res = serve_rec.run_pipeline(cfg, batch=batch, batches=batches,
                                 mode="sequential", fence=True)
    return res


def test_attribution_modeled_total_matches_cost_model_predict():
    res = _serve_session()
    from repro.configs import registry
    from repro.launch import serve_rec

    cfg = registry.get_dlrm("dlrm-qr-smoke")
    state = serve_rec.build_serve_state(cfg, shards=4, alpha=1.05, seed=0)
    att = A.attribute(obs.tracer().events, res["traffic_report"], state.eplan,
                      batch=4, fenced=True)
    # the decomposition is complete: cost-model stage terms sum to the
    # model's own prediction of the feature vector
    from repro.tune.cost_model import FEATURES

    feats = tuple(att.features[f] for f in FEATURES)
    assert att.modeled_total_s() == pytest.approx(
        att.model.predict(feats), rel=1e-9)
    # fenced session: every serving stage was measured
    measured = {r.stage for r in att.rows if r.measured_s is not None}
    assert {"prefetch", "pack", "h2d", "dispatch", "device_compute",
            "interact"} <= measured
    assert att.bottleneck in measured
    # shares sum to 1 over measured rows
    assert sum(r.share for r in att.rows if r.share is not None) \
        == pytest.approx(1.0)
    # bytes-bearing rows report both achieved and modeled GB/s
    dc = next(r for r in att.rows if r.stage == "device_compute")
    assert dc.bytes_per_batch > 0
    assert dc.achieved_gbps > 0 and dc.modeled_gbps > 0
    assert dc.residual_s == pytest.approx(dc.measured_s - dc.modeled_s)
    lr = att.largest_residual
    assert lr is not None and abs(lr["residual_s"]) <= att.total_s
    json.dumps(att.describe())
    assert att.describe()["schema"] == A.SCHEMA


def test_analytic_cost_model_prices_from_chip_constants():
    from repro.launch.mesh import V5E, peaks
    from repro.tune.cost_model import FEATURES
    from repro.tune.tuner import DISPATCH_OVERHEAD_S

    m = A.analytic_cost_model()
    coef = dict(zip(FEATURES, m.coef))
    assert coef["dispatches"] == DISPATCH_OVERHEAD_S
    chip = peaks(V5E)
    assert coef["hbm_bytes"] == pytest.approx(1.0 / chip.hbm_bw)
    assert coef["comm_bytes"] == pytest.approx(1.0 / (2 * chip.ici_bw_per_link))
    assert m.source == "analytic"


def test_model_terms_shared_with_roofline():
    """benchmarks/roofline.terms must price bytes/flops exactly like the
    serving attribution's model_terms (one source of truth)."""
    from benchmarks import roofline

    rec = {"status": "run", "mesh": "1pod", "chips": 4, "model_flops": 1e12,
           "hlo": {"flops": 4e12, "bytes": 8e9, "coll_wire_total": 1e9}}
    t = roofline.terms(rec)
    shared = A.model_terms(flops=4e12, hbm_bytes=8e9, wire_bytes=1e9)
    for k in ("compute_s", "memory_s", "collective_s", "step_s", "dominant"):
        assert t[k] == shared[k]
    rows = A.term_rows(shared, hbm_bytes=8e9, wire_bytes=1e9)
    assert [r["stage"] for r in rows] == ["compute", "memory", "collective"]
    assert all(r["basis"] == "roofline" for r in rows)
    mem = rows[1]
    assert mem["modeled_gbps"] == pytest.approx(
        8e9 / mem["modeled_s"] / 1e9)


# ---------------------------------------------------------------------------
# the serving-report artifact
# ---------------------------------------------------------------------------

def test_report_build_render_write(tmp_path):
    res = _serve_session()
    from repro.configs import registry
    from repro.launch import serve_rec

    cfg = registry.get_dlrm("dlrm-qr-smoke")
    state = serve_rec.build_serve_state(cfg, shards=4, alpha=1.05, seed=0)
    att = A.attribute(obs.tracer().events, res["traffic_report"], state.eplan,
                      batch=4, fenced=True)
    eng = SLOEngine(SLOSpec(p99_latency_s=1e-9, fast_window=1, slow_window=1,
                            qps_floor=1e9))
    for lat in res["latencies_s"]:
        eng.observe(lat)
    eng.finalize(hit_rate=res["hit_rate"], qps=res["qps"])
    rep = R.build(
        snapshot=obs.snapshot(), slo_state=eng.state(), attribution=att,
        traffic=res["traffic"],
        results={"sequential": {k: v for k, v in res.items()
                                if k not in ("logits", "latencies_s",
                                             "traffic_report")}},
        flight_dumps=[{"path": "f.json", "reason": "slo_burn:page",
                       "trigger_batch": 2, "records": 3}],
        meta={"config": cfg.name},
    )
    assert rep["schema"] == R.SCHEMA
    json.dumps(rep)
    md_path, jpath = R.write(rep, str(tmp_path / "report.md"), attribution=att)
    md = open(md_path).read()
    assert "**BREACHED**" in md
    assert f"**{att.bottleneck}" in md      # bottleneck named
    assert "achieved GB/s" in md and "modeled GB/s" in md
    assert "slo_burn:page" in md
    stored = json.load(open(jpath))
    assert stored["attribution"]["bottleneck"] == att.bottleneck
    # a stored report re-renders without the live Attribution object,
    # producing the same table
    re_md = R.render_markdown(stored)
    assert re_md.rstrip("\n") == md.rstrip("\n")


# ---------------------------------------------------------------------------
# end-to-end: serve_rec + observatory -> flight dump matches tracer spans
# ---------------------------------------------------------------------------

def test_pipeline_breach_dumps_flight_window_matching_tracer(tmp_path):
    from repro.configs import registry
    from repro.launch import serve_rec

    cfg = registry.get_dlrm("dlrm-qr-smoke")
    obs.enable()
    eng = SLOEngine(SLOSpec(p99_latency_s=1e-9, objective=0.99,
                            fast_window=2, slow_window=4))
    rec = FlightRecorder(capacity=16, out_dir=str(tmp_path))
    obs.install_observatory(slo=eng, recorder=rec)
    res = serve_rec.run_pipeline(cfg, batch=4, batches=6, mode="sequential",
                                 fence=True)
    # every steady-state batch was bad -> page alert -> at least one dump
    assert eng.n == len(res["latencies_s"]) == 5
    assert eng.breached and rec.dumps
    doc = json.load(open(rec.dumps[0]["path"]))
    assert doc["records"], "dump carries the ring"
    # each dumped record's stage durations equal the tracer's span durations
    # for that batch (sum over spans, us -> s), wrapper span excluded
    spans: dict = {}
    for ev in obs.tracer().events:
        if ev.get("ph") != "X" or ev["name"] == "batch":
            continue
        b = ev.get("args", {}).get("batch")
        if b is None:
            continue
        spans.setdefault(int(b), {}).setdefault(ev["name"], 0.0)
        spans[int(b)][ev["name"]] += ev["dur"] * 1e-6
    for r in doc["records"]:
        assert r["stages"], f"batch {r['batch']} record has no stages"
        assert r["stages"] == pytest.approx(spans[r["batch"]])
        # first steady-state record's delta also covers the warm-up dispatch
        expect = 2 if r["batch"] == 1 else 1
        assert r["counters"].get("engine/dispatch/serve_gather") == expect
    # the facade returned the observatory verdicts to the loop
    assert obs.observatory() is not None
    state = obs.observatory().state()
    assert state["slo"]["breached"] and state["flight_dumps"]


# ---------------------------------------------------------------------------
# satellite: serve_rec percentiles come from obs.metrics
# ---------------------------------------------------------------------------

def test_serve_rec_percentiles_are_the_shared_helper():
    from repro.launch import serve_rec
    from repro.obs.metrics import exact_percentile, latency_percentiles

    assert serve_rec._percentiles is obs.latency_percentiles
    samples = [0.001, 0.002, 0.003, 0.010, 0.020]
    got = latency_percentiles(samples)
    assert set(got) == {"lat_p50_s", "lat_p95_s", "lat_p99_s"}
    for q in (50, 95, 99):
        assert got[f"lat_p{q:g}_s"] == pytest.approx(
            np.percentile(samples, q))
    assert exact_percentile([], 99) == 0.0
