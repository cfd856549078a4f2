"""The yardstick's counts against hand counts, and the benchmark's copies of
the model's index split and weight layout against the program's."""

from __future__ import annotations

import jax
import numpy as np
import pytest

from bench import counts, model as model_mod, peaks
from bench.reference import dlrm as reference

DATA = model_mod.DIR.parent / "tests" / "data" / "configs"


def _model(name):
    root = DATA if name.endswith("-smoke") else None
    return model_mod.load(name, root)


@pytest.mark.parametrize("name", ["dlrm-qr", "dlrm-tt", "dlrm-qr-smoke",
                                  "dlrm-tt-smoke", "dlrm-dense-smoke"])
def test_big_rows_match_the_program(name):
    from repro.configs import registry
    from repro.engine import big_rows
    from repro.models import dlrm

    m = _model(name)
    cfg = registry.get_dlrm(m.registry_id)
    model_mod.check_program_config(m, cfg)
    emb = dlrm.make_bags(cfg)[0].emb
    ids = np.random.default_rng(0).integers(0, m.vocab_per_table, (64, m.pooling))
    if m.kind == "qr":
        ours = np.asarray(reference.qr_split(ids, m.collision)[0])
    elif m.kind == "tt":
        ours = np.asarray(reference.tt_split(ids, *m.vocab_factors[1:])[1])
    else:
        ours = ids                      # a dense table's row is the id
    np.testing.assert_array_equal(ours, big_rows(ids.astype(np.int32), emb))


@pytest.mark.parametrize("name", ["dlrm-qr-smoke", "dlrm-tt-smoke",
                                  "dlrm-dense-smoke"])
def test_weights_have_the_program_layout(name):
    from repro.configs import registry
    from repro.models import dlrm

    m = _model(name)
    cfg = registry.get_dlrm(m.registry_id)
    want = jax.eval_shape(lambda k: dlrm.init_dlrm(k, cfg)[0],
                          jax.random.PRNGKey(0))
    got = model_mod.make_params(m, 2**40 + 3)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (g.shape, g.dtype) == (w.shape, w.dtype)
    again = model_mod.make_params(m, 2**40 + 3)
    other = model_mod.make_params(m, 3)       # same low 32 bits
    leaf = lambda p: np.asarray(p["tables"][0][next(iter(p["tables"][0]))])
    np.testing.assert_array_equal(leaf(got), leaf(again))
    assert not np.array_equal(leaf(got), leaf(other))


def test_program_config_mismatch_is_refused(tmp_path):
    import json

    raw = json.loads((DATA / "dlrm-qr-smoke.json").read_text())
    raw["pooling"] = 9
    (tmp_path / "bad.json").write_text(json.dumps(raw))
    m = model_mod.load("bad", tmp_path)
    from repro.configs import registry

    with pytest.raises(ValueError, match="differs"):
        model_mod.check_program_config(m, registry.get_dlrm(m.registry_id))


@pytest.mark.parametrize("registry_id, ok", [
    ("dlrm-dense-smoke", True), ("dlrm-qr-smoke", False),
    ("dlrm-dense", False)])
def test_dense_config_against_the_registry(tmp_path, registry_id, ok):
    import json

    from repro.configs import registry

    raw = json.loads((DATA / "dlrm-dense-smoke.json").read_text())
    raw["registry_id"] = registry_id
    (tmp_path / "d.json").write_text(json.dumps(raw))
    m = model_mod.load("d", tmp_path)
    assert (m.kind, m.table_shapes()) == ("dense", {"table": (4096, 32)})
    if ok:
        model_mod.check_program_config(m, registry.get_dlrm(registry_id))
    else:
        with pytest.raises(ValueError, match="differs"):
            model_mod.check_program_config(m, registry.get_dlrm(registry_id))


@pytest.mark.parametrize("change, match", [
    ({"embedding": {"kind": "dense", "collision": 8}}, "no compression keys"),
    ({"embedding": {"kind": "hashed"}}, "unknown embedding kind"),
    ({"param_dtype": "bfloat16"}, "knows only")])
def test_malformed_dense_config_is_refused(tmp_path, change, match):
    import json

    raw = json.loads((DATA / "dlrm-dense-smoke.json").read_text())
    (tmp_path / "bad.json").write_text(json.dumps({**raw, **change}))
    with pytest.raises(ValueError, match=match):
        model_mod.load("bad", tmp_path)


# Two tables, two bags, three ids a bag, at the QR smoke shapes (collision 8,
# dim 32) and TT smoke shapes (factors 8 x 64 x 8, dims 4 x 4 x 2, rank 4).
IDS = np.array([[[0, 1, 9], [8, 8, 100]],
                [[1, 17, 0], [8, 4095, 100]]], dtype=np.int32)


def test_qr_gather_counts_by_hand():
    m = _model("dlrm-qr-smoke")
    c = counts.gather_counts(IDS, m)
    # table 0 ids {0,1,9,17}: q rows {0,1,2}, r rows {0,1}
    # table 1 ids {8,100,4095}: q rows {1,12,511}, r rows {0,4,7}
    rows = (3 + 2) + (3 + 3)
    assert c["bytes"] == rows * 32 * 4 + IDS.size * 4 + 2 * 2 * 32 * 4
    assert c["flops"] == (4 + 3) * 32 + 2 * 2 * 2 * 32
    assert c["pooled_bytes"] == 2 * 2 * 32 * 4


def test_tt_gather_counts_by_hand():
    m = _model("dlrm-tt-smoke")
    c = counts.gather_counts(IDS, m)
    # i = (i1*64 + i2)*8 + i3.  table 0 ids {0,1,9,17}: i1 {0}, i2 {0,1,2},
    # i3 {0,1}, (i1,i2) 3, (i2,i3) {(0,0),(0,1),(1,1),(2,1)} 4.
    # table 1 ids {8,100,4095}: i1 {0,7}, i2 {1,12,63}, i3 {0,4,7},
    # (i1,i2) 3, (i2,i3) 3.
    r, (d1, d2, d3) = 4, (4, 4, 2)
    row_bytes = ((1 + 2) * d1 * r + (3 + 3) * r * d2 * r + (2 + 3) * r * d3) * 4
    assert c["bytes"] == row_bytes + IDS.size * 4 + 2 * 2 * 32 * 4
    nid, n12, n23 = 4 + 3, 3 + 3, 4 + 3
    left = n12 * 2 * d1 * r * d2 * r + nid * 2 * d1 * d2 * r * d3
    right = n23 * 2 * r * d2 * r * d3 + nid * 2 * d1 * r * d2 * d3
    assert (left, right) == (6 * 512 + 7 * 256, 7 * 256 + 7 * 256)
    assert c["flops"] == min(left, right) + 2 * 2 * 2 * 32


def test_dense_gather_counts_by_hand():
    m = _model("dlrm-dense-smoke")
    c = counts.gather_counts(IDS, m)
    # table 0 ids {0,1,9,17}, table 1 ids {8,100,4095}: 7 rows read, none rebuilt
    assert c["bytes"] == 7 * 32 * 4 + IDS.size * 4 + 2 * 2 * 32 * 4
    assert c["flops"] == 2 * 2 * 2 * 32
    assert c["pooled_bytes"] == 2 * 2 * 32 * 4


def test_counts_of_an_unknown_kind_raise():
    import dataclasses

    m = dataclasses.replace(_model("dlrm-dense-smoke"), kind="hashed")
    with pytest.raises(ValueError, match="no counts"):
        counts.gather_counts(IDS, m)


def test_dense_reference_is_a_lookup_and_sum():
    m = _model("dlrm-dense-smoke")
    params = model_mod.make_params(m, 2**40 + 9)
    rng = np.random.default_rng(4)
    idx = rng.integers(0, m.vocab_per_table, (16, m.num_tables, m.pooling))
    dense = rng.standard_normal((16, m.num_dense)).astype(np.float32)
    logits, pooled = reference.forward_with_pooled(params, dense, idx, m)
    want = np.stack([np.asarray(t["table"], np.float64)[idx[:, i]].sum(axis=1)
                     for i, t in enumerate(params["tables"])], axis=1)
    assert pooled.shape == want.shape == (16, m.num_tables, m.dim)
    np.testing.assert_allclose(np.asarray(pooled), want, rtol=0,
                               atol=1e-6 * np.abs(want).max())
    # the head on the pooled rows, as one program with the lookup
    with jax.default_matmul_precision("highest"):
        whole = reference._forward(params, dense, idx, m, "f32")[0]
    np.testing.assert_allclose(np.asarray(logits), np.asarray(whole),
                               rtol=1e-6, atol=1e-6)


def test_head_and_step_counts_by_hand():
    m = _model("dlrm-qr-smoke")
    h = counts.head_counts(2, m)
    # bottom 13-64-32; F = 5 features -> 10 dots of dim 32; top 42-64-1
    mats = [(13, 64), (64, 32), (42, 64), (64, 1)]
    assert m.top_in == 42
    assert h["flops"] == 2 * (sum(2 * i * o for i, o in mats) + 10 * 2 * 32)
    weights = sum(i * o + o for i, o in mats) * 4
    assert h["bytes"] == weights + 2 * 4 * 32 * 4 + 2 * 13 * 4 + 2 * 4
    g = counts.gather_counts(IDS, m)
    s = counts.step_counts(g, h)
    assert s["flops"] == g["flops"] + h["flops"]
    # the pooled (B, T, dim) buffer is neither written nor read in one step
    assert s["bytes"] == g["bytes"] + h["bytes"] - 2 * 2 * 32 * 4 - 2 * 4 * 32 * 4


def test_full_size_counts_are_lower_bounds_of_the_reads():
    m = _model("dlrm-qr")
    ids = np.random.default_rng(1).integers(0, m.vocab_per_table, (64, 26, 32))
    c = counts.gather_counts(ids, m)
    every_access = ids.size * 2 * m.dim * 4      # a Q row and an R row each
    assert 0 < c["bytes"] - ids.size * 4 - 64 * 26 * 128 * 4 <= every_access


def test_peaks_and_least_time():
    pk = peaks.peaks("TPU v5 lite")
    assert peaks.least_time_s(197e12, 1.0, pk) == (1.0, "flops")
    assert peaks.least_time_s(1.0, 819e9, pk) == (1.0, "bytes")
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("cpu")
