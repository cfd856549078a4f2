"""The reduction from a profiler trace to device numbers, on hand-made
intervals and on a small trace recorded on a TPU v5e."""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench import trace

# ops (ns): two overlap, one runs past the window's end
OPS = [["fusion.1", 100, 50], ["custom-call.2", 120, 80], ["fusion.3", 400, 100],
       ["fusion.4", 950, 100]]
MODULES = [["jit__serve_gather_jit(7)", 100, 100], ["jit__head_jit(9)", 400, 100],
           ["jit__serve_gather_jit(7)", 950, 100]]
SPANS = [["prefetch", 0, 90, {"batch": 1, "depth": 1}],
         ["pack", 210, 150, {"batch": 1, "depth": 1}],
         ["batch", 0, 900, {"batch": 1, "depth": 0}],
         ["tail_sync", 900, 100, {"depth": 0}],
         ["compile_warmup", -500, 400, {"depth": 0}]]
DEV = {"ops": OPS, "modules": MODULES}


def test_union_and_busy():
    assert trace.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    # [100, 200) + [400, 500) + [950, 1000) inside [0, 1000)
    assert trace.busy_ns(OPS, 0, 1000) == 100 + 100 + 50


def test_window_from_spans():
    assert trace.window_of(SPANS) == (0, 1000)


def test_module_times():
    assert trace.module_ns(MODULES, "serve_gather", 0, 1000) == (200, 2)
    assert trace.module_ns(MODULES, "_head_jit", 0, 1000) == (100, 1)
    assert trace.module_ns(MODULES, "serve_gather", 0, 900) == (100, 1)
    assert trace.module_name("jit__head_jit(9)") == "jit__head_jit"


def test_top_ops_name_their_module():
    top = dict(trace.top_ops(DEV, 0, 1000))
    assert top == pytest.approx({"jit__serve_gather_jit/custom-call.2": 80e-9,
                   "jit__serve_gather_jit/fusion.1": 50e-9,
                   "jit__head_jit/fusion.3": 100e-9,
                   "jit__serve_gather_jit/fusion.4": 50e-9})


def test_idle_gaps_by_host_stage():
    # gaps: [0,100) prefetch covers 90; [200,400) pack covers 150;
    # [500,950) pack 0..360 no; tail_sync covers [900,950) -> tail_sync
    gaps = dict(trace.idle_gaps(DEV, SPANS, 0, 1000))
    assert gaps == pytest.approx({"prefetch": 100e-9, "pack": 200e-9,
                                  "tail_sync": 450e-9})


def test_spans_move_onto_the_trace_clock():
    events = [{"name": "pack", "ph": "X", "ts": 2.0, "dur": 3.0,
               "args": {"batch": 1}}, {"name": "c", "ph": "C", "ts": 0}]
    # perf_counter origin 10.0 s; the mark read 10.001 s at trace 5,000 ns
    out = trace.spans_on_trace_clock(events, 10.0, 10.001, 5_000)
    assert out == [["pack", pytest.approx(5_000 - 1e6 + 2e3), 3e3, {"batch": 1}]]


def test_summary_of_a_window():
    tr = {"devices": {"/device:TPU:0": DEV}, "host": [[trace.SYNC, 0, 1]]}
    s = trace.summarize(tr, SPANS)
    assert s["window_s"] == pytest.approx(1000e-9)
    assert s["busy_s"] == pytest.approx(250e-9)
    assert trace.summarize({"devices": {}, "host": []}, SPANS) is None


# Three overlap-mode batches of qr-zipf-2k recorded on one TPU v5e
# (a traced run of the harness), cut to the window of batches 1-3.
RECORDED = json.loads(
    (Path(__file__).parent / "data" / "trace-qr-zipf-2k.json").read_text())


def _busy_by_grid(ops, lo, hi, step=1000.0):
    """Busy time counted on a 1 us grid: independent of the interval union."""
    import numpy as np

    n = int((hi - lo) // step) + 1
    on = np.zeros(n, bool)
    for _name, s, d in ops:
        a = min(n, max(0, int((s - lo) // step)))
        b = min(n, max(0, int((s + d - lo) // step)))
        on[a:b] = True
    return on.sum() * step


def test_recorded_trace_reduces_to_its_own_numbers():
    tr, spans = RECORDED["trace"], RECORDED["spans"]
    dev = tr["devices"]["/device:TPU:0"]
    lo, hi = trace.window_of(spans)
    assert (lo, hi) == (min(s[1] for s in spans), max(s[1] + s[2] for s in spans))
    busy = trace.busy_ns(dev["ops"], lo, hi)
    assert busy == pytest.approx(_busy_by_grid(dev["ops"], lo, hi), rel=1e-3)
    ns, count = trace.module_ns(dev["modules"], "serve_gather", lo, hi)
    assert count == 2      # the gathers of batches 1 and 2; batch 0 ran before
    assert ns == sum(d for n, s, d in dev["modules"]
                     if "serve_gather" in n and lo <= s < hi)
    s = trace.summarize(tr, spans)
    assert s["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert 0 < s["busy_s"] <= s["window_s"]
    names = [n for n, _ in s["breakdown"]["device_ops"]]
    assert names[0].startswith("jit__serve_gather_jit/")
    assert all(" = " not in n for n in names)
    idle = sum(v for _, v in s["breakdown"]["idle_gaps"])
    assert idle == pytest.approx(s["window_s"] - s["busy_s"], rel=1e-9)
    assert trace.sync_ns(tr) < lo


def test_summary_reads_only_the_cells_chips():
    tr, spans = RECORDED["trace"], RECORDED["spans"]
    lo, hi = trace.window_of(spans)
    # a second chip, busy all through the window, listed before chip 0
    other = {"ops": [["fusion.99", lo, hi - lo]],
             "modules": [["jit_other(1)", lo, hi - lo]]}
    two = {**tr, "devices": {trace.device_plane(1): other, **tr["devices"]}}
    one = trace.summarize(tr, spans)
    assert trace.summarize(two, spans, 1) == one
    assert trace.summarize(two, spans) == one
    both = trace.summarize(two, spans, 2)
    assert both["busy_s"] == pytest.approx((one["busy_s"] + (hi - lo) * 1e-9) / 2)
    assert both["modules"] == one["modules"]
    assert both["breakdown"] == one["breakdown"]
    with pytest.raises(ValueError, match="no plane"):
        trace.summarize(tr, spans, 2)
