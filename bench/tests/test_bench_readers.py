"""The readers of the prefetch phases and the gather's time per grid step,
on a hand-made ``Context``: their values, and None where the program or the
trace gives them nothing to read."""

from __future__ import annotations

import pytest

from bench import harness

METRICS = harness.BENCH / "metrics"


def read(name, ctx):
    return harness.reader(harness.reader_path(METRICS, name))(ctx)


def span(name, start, dur, **args):
    return [name, start, dur, args]


def spans(grid=(1000, 1000)):
    """Batch 0 (untimed) and window batches 1 and 2, times in ns."""
    out = []
    for t, steps in zip((0, 1, 2), (1000, *grid)):
        base = 10_000 * t
        out += [span("prefetch", base, 5000, batch=t),
                span("cache_rank", base + 100, 3000, batch=t),
                span("cache_update", base + 3200, 1500 + t, batch=t,
                     staged=4, kept=2, evicted=3),
                span("pack", base + 5000, 500, batch=t),
                span("dispatch", base + 6000, 200, batch=t,
                     **({} if steps is None else {"grid_steps": steps}))]
    return out + [span("tail_sync", 30_000, 100)]


def ctx(spans, modules):
    trace = None if modules is None else {
        "modules": modules, "lo": 10_000, "hi": 30_100}
    return harness.Context(model=None, result={}, window=[{}, {}],
                           setup_s=0.0, spans=spans, trace=trace, peaks=None)


GATHERS = [["jit__serve_gather_jit(3)", 6500, 400],      # batch 0: outside
           ["jit__serve_gather_jit(3)", 16_500, 300],
           ["jit__serve_gather_jit(3)", 26_500, 500],
           ["jit__head_jit(4)", 27_000, 50]]


@pytest.mark.parametrize("suffix", ["tput", "lat"])
def test_phase_readers(suffix):
    c = ctx(spans(), GATHERS)
    assert read(f"cache_rank_ms.{suffix}", c) == pytest.approx(3000e-6)
    assert read(f"cache_update_ms.{suffix}", c) == pytest.approx(3003e-6 / 2)


@pytest.mark.parametrize("suffix", ["tput", "lat"])
def test_gather_step_reader(suffix):
    c = ctx(spans(grid=(1000, 3000)), GATHERS)
    # (300 + 500) ns over 1000 + 3000 steps of window batches 1 and 2
    assert read(f"gather_step_ns.{suffix}", c) == pytest.approx(0.2)


def test_readers_give_none_without_the_programs_spans():
    old = [s for s in spans() if not s[0].startswith("cache_")]
    assert read("cache_rank_ms.lat", ctx(old, GATHERS)) is None
    assert read("cache_update_ms.tput", ctx(old, GATHERS)) is None
    # dispatch spans of a program that does not count its grid steps
    assert read("gather_step_ns.tput", ctx(spans(grid=(None, None)), GATHERS)) is None
    assert read("gather_step_ns.tput", ctx(spans(grid=(1000, None)), GATHERS)) is None


def test_gather_step_reader_needs_one_module_run_a_dispatch():
    assert read("gather_step_ns.lat", ctx(spans(), None)) is None      # no trace
    assert read("gather_step_ns.lat", ctx(spans(), GATHERS[:2])) is None
    extra = GATHERS + [["jit__serve_gather_jit(3)", 29_000, 100]]
    assert read("gather_step_ns.lat", ctx(spans(), extra)) is None
    assert read("gather_step_ns.lat", ctx([], GATHERS)) is None
