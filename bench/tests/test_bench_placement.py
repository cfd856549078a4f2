"""The cell's chips: weights placed row-sharded for a cell on more than one
chip, the reference on them, the roofline priced at the cell's chips, and a
pin that one-chip cells get the weights and reference outputs they had before
placement existed."""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from bench import harness, model as model_mod, peaks
from bench.reference import dlrm as reference
from bench.traffic import generator

DATA = harness.BENCH / "tests" / "data"
PIN_SEED = 2**33 + 7


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(np.asarray(a))
        h.update(str((a.shape, a.dtype.str)).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", ["dlrm-qr-smoke", "dlrm-tt-smoke"])
def test_unplaced_weights_and_reference_are_pinned(name):
    # digests recorded before dense tables and placement were added
    want = json.loads((DATA / "pin-qr-tt.json").read_text())[name]
    m = model_mod.load(name, DATA / "configs")
    params = model_mod.make_params(m, PIN_SEED)
    batch = generator.dlrm_batch(m, 16, seed=PIN_SEED, step=1, alpha=0.99)
    logits, pooled = reference.forward_with_pooled(
        params, batch["dense"], batch["idx"], m)
    got = {"params": _digest(jax.tree.leaves(params)),
           "logits": _digest([logits]), "pooled": _digest([pooled])}
    assert got == want


def _window(m, batches=3, batch=8):
    return [generator.dlrm_batch(m, batch, seed=11, step=t, alpha=0.99)
            for t in range(batches)]


@pytest.mark.parametrize("name", ["dlrm-qr-smoke", "dlrm-tt-smoke",
                                  "dlrm-dense-smoke"])
def test_least_time_is_priced_at_the_cells_chips(name):
    from bench import counts

    m = model_mod.load(name, DATA / "configs")
    pk = peaks.peaks("TPU v5 lite")
    gathers = [["jit__serve_gather_jit(1)", 10 * t, 5] for t in range(3)]
    trace = {"modules": gathers, "lo": 0, "hi": 10**9, "window_s": 1.0}
    kw = dict(model=m, result={}, window=_window(m), setup_s=0.0, spans=[],
              trace=trace, peaks=pk)
    one, four = harness.Context(**kw), harness.Context(**kw, chips=4)
    by_hand = 0.0
    for b in _window(m):
        c = counts.gather_counts(np.asarray(b["idx"]), m)
        by_hand += max(c["bytes"] / pk["hbm_bytes_per_s"], c["flops"] / pk["flops"])
    assert one.chips == 1 and one.least_s("gather") == by_hand
    for part in ("gather", "step"):
        assert four.least_s(part) == one.least_s(part) / 4
    for metric in ("gather_roofline.tput", "step_mfu.tput"):
        read = harness.reader(harness.reader_path(harness.BENCH / "metrics", metric))
        assert read(four) == read(one) / 4


# Runs with four host devices: a four-chip cell of "dlrm-dense-smoke", its
# tables row-sharded, resolved through ``prepare`` and served through
# ``calibrate``.
CHILD = r"""
import json, sys
from pathlib import Path
import jax
import numpy as np
from bench import calibrate, harness, model as model_mod
from bench.reference import dlrm as reference
from bench.tests import test_bench_run as smoke

tmp = Path(sys.argv[1])
spec = smoke.smoke_spec()
spec["workloads"].append({"name": "dense-4chip", "config": "dlrm-dense-smoke",
                          "traffic": "smoke-zipf", "chips": 4, "why": "test"})
dirs = harness.Dirs(configs=smoke.DATA / "configs",
                    traffic=smoke.DATA / "traffic", limits=tmp / "limits")
c = harness.prepare("dense-4chip", spec=spec, dirs=dirs, require_tpu=False)
seed = 2**40 + 21
placed = model_mod.make_params(c.model, seed, c.devices)
plain = model_mod.make_params(c.model, seed)
one = model_mod.make_params(c.model, seed, c.devices[:1])
out = {"devices": len(c.devices), "tables": [],
       "one_chip_devices": sorted({len(x.sharding.device_set)
                                   for x in jax.tree.leaves(one)}),
       "one_chip_equal": all(np.array_equal(np.asarray(a), np.asarray(b))
                             for a, b in zip(jax.tree.leaves(one),
                                             jax.tree.leaves(plain))),
       "mlp_replicated": all(
    x.sharding.is_fully_replicated and len(x.sharding.device_set) == 4
    for part in ("bottom", "top") for layer in placed[part]
    for x in layer.values())}
for got, want in zip(placed["tables"], plain["tables"]):
    x = got["table"]
    out["tables"].append({
        "spec": [str(a) for a in x.sharding.spec],
        "mesh": [list(x.sharding.mesh.axis_names), x.sharding.mesh.size],
        "shard_rows": sorted({s.data.shape[0] for s in x.addressable_shards}),
        "equal": bool(np.array_equal(np.asarray(x), np.asarray(want["table"])))})
batch = c.mix["batch"]
rng = np.random.default_rng(3)
idx = rng.integers(0, c.model.vocab_per_table,
                   (batch, c.model.num_tables, c.model.pooling))
dense = rng.standard_normal((batch, c.model.num_dense)).astype(np.float32)
gaps = {}
for precision in reference.PRECISIONS:
    lp, pp = reference.forward_with_pooled(placed, dense, idx, c.model, precision)
    lu, pu = reference.forward_with_pooled(plain, dense, idx, c.model, precision)
    pu, lu = np.asarray(pu, np.float64), np.asarray(lu, np.float64)
    gaps[precision] = {
        "pooled": float(np.abs(np.asarray(pp) - pu).max() / np.abs(pu).max()),
        "logits": float(np.abs(np.asarray(lp) - lu).max() / np.abs(lu).max())}
out["gaps"] = gaps
out["calibrate"] = list(calibrate.readings(c, [seed], 1e-6, 1))
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def four_chips(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("dense-4chip")
    (tmp / "limits").mkdir()
    (tmp / "limits" / "dense-4chip.json").write_text(
        (DATA / "limits" / "dense-smoke.json").read_text())
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.pathsep.join(
               [str(harness.ROOT), str(harness.ROOT / "src")])}
    out = subprocess.run([sys.executable, "-c", CHILD, str(tmp)], env=env,
                         cwd=harness.ROOT, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_four_chip_cell_resolves_through_prepare(four_chips):
    assert four_chips["devices"] == 4


def test_tables_are_row_sharded_on_model(four_chips):
    assert len(four_chips["tables"]) == 4
    for t in four_chips["tables"]:
        assert t["spec"] == ["model", "None"]
        assert t["mesh"] == [["model"], 4]
        assert t["shard_rows"] == [4096 // 4]
    assert four_chips["mlp_replicated"]


def test_placed_weights_equal_the_unplaced(four_chips):
    assert all(t["equal"] for t in four_chips["tables"])


def test_one_chip_weights_are_not_placed(four_chips):
    assert four_chips["one_chip_devices"] == [1]
    assert four_chips["one_chip_equal"]


@pytest.mark.parametrize("precision", reference.PRECISIONS)
def test_reference_on_placed_weights_matches_one_device(four_chips, precision):
    gaps = four_chips["gaps"][precision]
    assert gaps["pooled"] <= 1e-6 and gaps["logits"] <= 1e-6


def test_calibrate_serves_the_four_chip_cell(four_chips):
    (rec,) = four_chips["calibrate"]
    assert rec["workload"] == "dense-4chip" and rec["chips"] == 4
    assert rec["batches"] == 2
    limits = json.loads((DATA / "limits" / "dense-smoke.json").read_text())
    for number, limit in limits.items():
        assert 0 <= rec[f"program.{number}"] <= limit
    assert rec["control.logit_max_abs_err"] > limits["logit_max_abs_err"]
    assert rec["bf16_tables.pooled_max_rel_err"] > limits["pooled_max_rel_err"]
