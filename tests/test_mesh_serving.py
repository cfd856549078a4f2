"""Serving tables row-sharded over a four-device ``model`` mesh through the
normal path (``serve_rec.run_pipeline``): each chip gathers from its own
rows and cache block, and only the pooled partials are summed across chips.

One child process with four host devices serves ``dlrm-dense-smoke`` and
``dlrm-qr-smoke`` on one device and on four, in both modes, and compares
the pooled embeddings fed to the head with a plain float64 reference (no
kernels, no cache) and with the one-device path on the same weights.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARCHS = ("dlrm-dense-smoke", "dlrm-qr-smoke")

CHILD = r"""
import dataclasses, json, sys
import jax, numpy as np
from repro import obs
from repro.configs import registry
from repro.data import synthetic
from repro.engine import engine as engine_mod
from repro.launch import serve_rec

SEED, BATCH, BATCHES = 5, 16, 3
out = {}


def plain_pooled(tables, idx, cfg):
    # sum_k of each id's row: the dense row, or the QR rows q[i // c] + r[i % c]
    res = np.zeros((idx.shape[0], cfg.num_tables, cfg.dim))
    for t, tab in enumerate(tables):
        ids = np.asarray(idx[:, t])
        if cfg.embedding_kind == "dense":
            rows = np.asarray(tab["table"], np.float64)[ids]
        else:
            c = cfg.qr_collision
            rows = (np.asarray(tab["q"], np.float64)[ids // c]
                    + np.asarray(tab["r"], np.float64)[ids % c])
        res[:, t] = rows.sum(axis=1)
    return res


def collectives(fn, *args):
    text = fn.lower(*args).compile().as_text()
    return {op: text.count(f" {op}(") for op in
            ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")}


for arch in sys.argv[1].split(","):
    # a small cache, so that both hits and misses are served
    cfg = dataclasses.replace(registry.get_dlrm(arch), cache_slots=16)
    state = serve_rec.build_serve_state(cfg, shards=4, alpha=0.99, seed=SEED)
    eng = state.engine
    rec = {"runs": {}}
    placed = serve_rec.init_params(cfg, SEED, 4)
    # made in place, the weights are the one-device ones up to rounding (one
    # jitted call against eager ops); the served comparison takes the placed
    # values onto one device
    rec["weights_gap"] = max(
        float(np.abs(np.asarray(a) - np.asarray(b)).max())
        for a, b in zip(jax.tree.leaves(serve_rec.init_params(cfg, SEED, 1)),
                        jax.tree.leaves(placed)))
    params = {1: jax.tree.map(
        lambda x: jax.device_put(np.asarray(x), jax.devices()[0]), placed),
              4: placed}
    big = "table" if cfg.embedding_kind == "dense" else "q"
    rec["row_mesh"] = [None if eng.row_mesh(params[c]["tables"]) is None
                       else dict(eng.row_mesh(params[c]["tables"]).shape)
                       for c in (1, 4)]
    packed = eng.pack(params[4]["tables"])
    rec["packed_shard_rows"] = sorted(
        {s.data.shape[0] for s in packed[big].addressable_shards})
    rec["packed_rows"] = eng.plan.layout.total_rows
    data = [synthetic.dlrm_batch(cfg, BATCH, seed=SEED, step=t, alpha=0.99)
            for t in range(BATCHES)]
    want = [plain_pooled(params[1]["tables"], np.asarray(b["idx"]), cfg)
            for b in data]
    head = serve_rec._head_jit
    for chips in (1, 4):
        for mode in ("overlap", "sequential"):
            kept = []

            def tap(p, d, pooled, c):
                kept.append(np.asarray(pooled, np.float64))
                return head(p, d, pooled, c)

            obs.enable()
            serve_rec._head_jit = tap
            try:
                res = serve_rec.run_pipeline(
                    cfg, batch=BATCH, batches=BATCHES, alpha=0.99, seed=SEED,
                    mode=mode, state=state, params=params[chips])
            finally:
                serve_rec._head_jit = head
            events = obs.tracer().events
            dispatch = [e["args"] for e in events if e["name"] == "dispatch"]
            reduce = [e for e in events if e["name"] == "reduce"]
            obs.disable()
            obs.registry().reset()
            obs.tracer().reset()
            rec["runs"][f"{chips}/{mode}"] = {
                "pooled": [k.tolist() for k in kept],
                "logits": [np.asarray(x, np.float64).tolist()
                           for x in res["logits"]],
                "ref_gap": max(float(np.abs(k - w).max() / np.abs(w).max())
                               for k, w in zip(kept, want)),
                "hit_rate": res["hit_rate"],
                "dispatch": dispatch, "reduce": len(reduce),
                "walked": eng.walked(BATCH),
            }
    mesh = eng.row_mesh(params[4]["tables"])
    idx = np.asarray(data[0]["idx"])
    slot = np.full(idx.shape, -1, np.int32)
    rows = np.zeros((4, sum(eng.plan.layout.slot_budgets)), np.int32)
    rec["gather_collectives"] = collectives(
        engine_mod._serve_gather_jit, packed, idx, slot, rows, eng.plan, mesh)
    partials = eng.serve_gather(packed, idx, slot, rows)
    rec["partials_shape"] = list(partials.shape)
    rec["reduce_collectives"] = collectives(
        engine_mod._reduce_pooled_jit, partials, mesh, "model")
    out[arch] = rec

tt = registry.get_dlrm("dlrm-tt-smoke")
tt_state = serve_rec.build_serve_state(tt, shards=4, alpha=0.99, seed=SEED)
try:
    tt_state.engine.pack(serve_rec.init_params(tt, SEED, 4)["tables"])
    out["tt_error"] = None
except NotImplementedError as e:
    out["tt_error"] = str(e)

path = sys.argv[2]
rc = serve_rec.main(["--arch", "dlrm-dense", "--tiny", "--chips", "4",
                     "--batches", "3", "--json", path])
out["cli"] = {"rc": rc, "records": json.load(open(path))}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    path = tmp_path_factory.mktemp("mesh") / "cli.json"
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.path.join(ROOT, "src")}
    out = subprocess.run([sys.executable, "-c", CHILD, ",".join(ARCHS),
                          str(path)], env=env, cwd=ROOT, capture_output=True,
                         text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _arr(x):
    import numpy as np

    return np.asarray(x, np.float64)


@pytest.mark.parametrize("arch", ARCHS)
def test_placed_weights_are_row_sharded(served, arch):
    rec = served[arch]
    assert rec["weights_gap"] <= 1e-6
    assert rec["row_mesh"] == [None, {"model": 4}]
    # each chip holds its quarter of every table's rows and one zero row
    assert rec["packed_shard_rows"] == [rec["packed_rows"] // 4 + 1]


@pytest.mark.parametrize("mode", ["overlap", "sequential"])
@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("arch", ARCHS)
def test_pooled_matches_the_plain_reference(served, arch, chips, mode):
    run = served[arch]["runs"][f"{chips}/{mode}"]
    assert len(run["pooled"]) == 3
    assert run["ref_gap"] <= 1e-5
    assert 0.0 < run["hit_rate"] < 1.0        # hits and misses both served


@pytest.mark.parametrize("mode", ["overlap", "sequential"])
@pytest.mark.parametrize("arch", ARCHS)
def test_four_chips_match_one_chip(served, arch, mode):
    runs = served[arch]["runs"]
    one, four = runs[f"1/{mode}"], runs[f"4/{mode}"]
    one_p, four_p = _arr(one["pooled"]), _arr(four["pooled"])
    assert abs(four_p - one_p).max() <= 1e-5 * abs(one_p).max()
    assert abs(_arr(four["logits"]) - _arr(one["logits"])).max() <= 1e-5


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("arch", ARCHS)
def test_logits_agree_between_modes(served, arch, chips):
    runs = served[arch]["runs"]
    assert (_arr(runs[f"{chips}/overlap"]["logits"])
            == _arr(runs[f"{chips}/sequential"]["logits"])).all()


@pytest.mark.parametrize("arch", ARCHS)
def test_dispatch_spans_carry_chips_walked_owned(served, arch):
    for chips in (1, 4):
        run = served[arch]["runs"][f"{chips}/overlap"]
        assert len(run["dispatch"]) == 3
        assert run["reduce"] == (3 if chips == 4 else 0)
        entries = 16 * 4 * 8                    # batch x tables x pooling
        for d in run["dispatch"]:
            assert d["chips"] == chips and d["walked"] == run["walked"]
            assert run["walked"] >= entries
            if chips == 1:
                assert d["owned"] == entries
            else:
                assert entries / 4 <= d["owned"] <= entries


@pytest.mark.parametrize("arch", ARCHS)
def test_only_pooled_partials_cross_chips(served, arch):
    rec = served[arch]
    assert sum(rec["gather_collectives"].values()) == 0
    assert rec["partials_shape"] == [4, 16, 4, rec["partials_shape"][-1]]
    assert rec["reduce_collectives"]["all-reduce"] >= 1
    assert rec["reduce_collectives"]["all-gather"] == 0


def test_tt_tables_on_a_mesh_are_refused(served):
    assert served["tt_error"] and "TT" in served["tt_error"]


def test_cli_serves_row_sharded_over_chips(served):
    assert served["cli"]["rc"] == 0
    (rec,) = served["cli"]["records"]
    assert rec["config"] == "dlrm-dense-smoke" and rec["served"] == 8 * 2
