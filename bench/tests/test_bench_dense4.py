"""The four-chip cell ``dense-zipf-2k-4chip``: its configuration against the
program's registry, its files, and the readers of the cross-chip reduction
and of the share of walked entries a chip owns, on a hand-made context."""

from __future__ import annotations

import json

import numpy as np
import pytest

from bench import harness, ici, model as model_mod, peaks
from bench.traffic import generator

CELL = "dense-zipf-2k-4chip"
METRICS = harness.BENCH / "metrics"


def read(name, ctx):
    return harness.reader(harness.reader_path(METRICS, name))(ctx)


def test_config_is_the_registrys_dlrm_dense():
    from repro.configs import registry

    m = model_mod.load("dlrm-dense")
    model_mod.check_program_config(m, registry.get_dlrm("dlrm-dense"))
    assert (m.kind, m.num_tables, m.vocab_per_table, m.dim, m.pooling) == (
        "dense", 26, 2_000_000, 128, 32)
    raw = json.loads((harness.BENCH / "configs" / "dlrm-dense.json").read_text())
    assert raw["reduced"] == [] and raw["plan_shards"] == 4
    assert set(raw["assumed"]) == {"vocab_per_table", "pooling"}
    # a chip's quarter of every table: 6.66 GB of the 16 GB
    (rows, dim), = m.table_shapes().values()
    assert m.num_tables * rows // 4 * dim * 4 == 6_656_000_000


def test_cell_resolves_its_files_and_asks_for_four_chips():
    spec = harness.load_spec()
    cell = harness.find_cell(spec, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "dlrm-dense", "zipf-2k", 4)
    model_mod.load(cell["config"])
    assert generator.load(cell["traffic"])["batch"] == 2048
    limits = json.loads((harness.BENCH / "limits" / f"{CELL}.json").read_text())
    assert limits["pooled_max_rel_err"] == 1e-4
    assert 0 < limits["logit_max_abs_err"] < 1
    names = {m["name"] for m in harness.cell_metrics(spec, CELL, True)}
    assert {"reduce_ms.host", "reduce_roofline.host", "owned_share.host",
            "idle_share.host", "gather_ms.host"} <= names
    assert [m["name"] for m in harness.cell_metrics(spec, CELL, False)] == [
        "samples_per_s.host", "setup_s"]


def test_reduce_bytes_are_pinned_by_hand():
    # 3/4 of one float32 (2048, 26, 128) partial: 27,262,976 B
    assert ici.reduce_bytes(2048, 26, 128, 4) == 20_447_232
    assert ici.reduce_bytes(2048, 26, 128, 1) == 0
    pk = peaks.peaks("TPU v5 lite")
    assert ici.ici_bytes_per_s(pk) == 200e9            # 1,600 Gbit/s
    assert ici.least_reduce_s(2048, 26, 128, 4, pk) == pytest.approx(102.23616e-6)
    with pytest.raises(KeyError):
        ici.ici_bytes_per_s({"flops": 1.0})


def _ctx(spans, modules, *, peaks_=None, batch=2048):
    m = model_mod.load("dlrm-dense")
    window = [{"idx": np.zeros((batch, 26, 32), np.int32)} for _ in range(2)]
    trace = None if modules is None else {"modules": modules, "lo": 10_000,
                                          "hi": 30_100, "window_s": 2e-5}
    return harness.Context(model=m, result={}, window=window, setup_s=0.0,
                           spans=spans, trace=trace, peaks=peaks_, chips=4)


def _spans(owned=(220_000, 230_000), walked=1_720_320):
    out = []
    for t, own in zip((0, 1, 2), (1, *owned)):
        args = {"batch": t, "grid_steps": 840, "chips": 4}
        if own is not None:
            args.update(walked=walked, owned=own)
        out.append(["dispatch", 10_000 * t + 6000, 200, args])
    return out


REDUCES = [["jit__reduce_pooled_jit(7)", 6500, 999],      # batch 0: outside
           ["jit__reduce_pooled_jit(7)", 16_500, 300_000],
           ["jit__reduce_pooled_jit(7)", 26_500, 500_000],
           ["jit__serve_gather_jit(3)", 27_000, 50]]


def test_reduce_readers():
    c = _ctx(_spans(), REDUCES, peaks_=peaks.peaks("TPU v5 lite"))
    assert read("reduce_ms.host", c) == pytest.approx(0.4)
    least = 2 * ici.least_reduce_s(2048, 26, 128, 4, c.peaks)
    assert read("reduce_roofline.host", c) == pytest.approx(100 * least / 800e-6)


def test_owned_share_reader():
    c = _ctx(_spans(), REDUCES)
    assert read("owned_share.host", c) == pytest.approx(
        100 * 450_000 / (2 * 1_720_320))


def test_readers_give_none_where_the_program_gives_nothing():
    # the parent: no reduction module, dispatch spans without walked/owned
    old = _ctx(_spans(owned=(None, None)), REDUCES[3:],
               peaks_=peaks.peaks("TPU v5 lite"))
    assert read("reduce_ms.host", old) is None
    assert read("reduce_roofline.host", old) is None
    assert read("owned_share.host", old) is None
    assert read("reduce_roofline.host", _ctx(_spans(), REDUCES)) is None  # CPU
    assert read("reduce_ms.host", _ctx(_spans(), None)) is None          # no trace
