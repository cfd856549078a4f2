"""A whole run of the harness on the CPU at the smoke sizes: the reference
agrees with ``run_pipeline``, the control and a broken timed path do not, and
new traffic mixes and metric readers are found by name."""

from __future__ import annotations

import copy
import json
import shutil
import sys
import time
import types

import numpy as np
import pytest

from bench import calibrate, harness, run as run_mod

DATA = harness.BENCH / "tests" / "data"
DIRS = harness.Dirs(configs=DATA / "configs", traffic=DATA / "traffic",
                    limits=DATA / "limits")
CELLS = {"qr-smoke": ("dlrm-qr-smoke", "smoke-zipf"),
         "tt-smoke": ("dlrm-tt-smoke", "smoke-seq")}
SECONDS = 1e-6          # the floor: two window batches, whatever the timing


def smoke_spec(extra_cells=()):
    spec = copy.deepcopy(harness.load_spec())
    spec["workloads"] = [
        {"name": n, "config": c, "traffic": t, "chips": 1, "why": "CPU test"}
        for n, (c, t) in [*CELLS.items(), *extra_cells]]
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    return spec


def run(cell, *, trace=False, spec=None, dirs=DIRS, seed=2**31 + 17):
    c = harness.prepare(cell, spec=spec or smoke_spec(), dirs=dirs,
                        require_tpu=False)
    return harness.run_cell(c, seed, SECONDS, trace, log=lambda _msg: None)


@pytest.fixture(scope="module")
def lines():
    return {cell: run(cell) for cell in CELLS}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_sound_run_is_correct(lines, cell):
    line = lines[cell]
    assert line["correct"] is True and line["failed"] == 0
    assert list(line)[-1] == "checks"
    assert list(line["checks"]) == ["logit_max_abs_err", "pooled_max_rel_err"]
    for gap in line["checks"].values():
        assert 0 <= gap["value"] <= gap["limit"]
    assert line["checks"]["logit_max_abs_err"]["value"] > 0
    assert line["attempted"] == 16 * 2
    assert line["device"]["platform"] == "cpu"
    assert {"samples_per_s", "batch_p95_ms", "setup_s"} <= set(line["metrics"])


@pytest.mark.parametrize("precision, number", [
    ("control", "logit_max_abs_err"), ("bf16_tables", "pooled_max_rel_err")])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_control_in_the_programs_place_fails(cell, precision, number):
    c = harness.prepare(cell, spec=smoke_spec(), dirs=DIRS, require_tpu=False)
    s = harness.serve(c, 5, SECONDS, False, process_start=time.time())
    assert sorted(s.pooled) == [0, 1]
    checks = calibrate.control_checks(s, c.model, c.limits, precision)
    assert checks[number]["value"] > c.limits[number]
    from bench.reference import dlrm as reference

    logits, pooled = [], {}
    for j, b in enumerate(s.window):
        lg, pl = reference.forward_with_pooled(s.params, b["dense"], b["idx"],
                                               c.model, precision)
        logits.append(np.asarray(lg))
        pooled[j] = np.asarray(pl)
    again, failed = harness.compare(s.params, s.window, logits, pooled,
                                    c.model, c.limits)
    assert again == checks and failed > 0


def _alter_logit(monkeypatch):
    from repro.launch import serve_rec

    head = serve_rec._head_jit

    def altered(params, dense, pooled, cfg):
        return head(params, dense, pooled, cfg).at[0].add(0.5)

    monkeypatch.setattr(serve_rec, "_head_jit", altered)


def _alter_pooled(monkeypatch):
    from repro.engine.engine import EmbeddingEngine

    gather = EmbeddingEngine.serve_gather

    def altered(self, *args):
        return gather(self, *args).at[0, 0].multiply(0.5)

    monkeypatch.setattr(EmbeddingEngine, "serve_gather", altered)


def _bf16_tables(monkeypatch):
    import jax.numpy as jnp

    from repro.engine.engine import EmbeddingEngine

    pack = EmbeddingEngine.pack

    def lowered(self, tables):
        return pack(self, [{k: v.astype(jnp.bfloat16).astype(jnp.float32)
                            for k, v in t.items()} for t in tables])

    monkeypatch.setattr(EmbeddingEngine, "pack", lowered)


@pytest.mark.parametrize("fault, number", [
    (_alter_logit, "logit_max_abs_err"), (_alter_pooled, "pooled_max_rel_err"),
    (_bf16_tables, "pooled_max_rel_err")])
def test_broken_timed_path_is_not_correct(monkeypatch, fault, number):
    fault(monkeypatch)
    line = run("qr-smoke")
    assert line["correct"] is False and line["failed"] > 0
    gap = line["checks"][number]
    assert gap["value"] > gap["limit"]


def test_program_that_bypasses_the_head_fails(monkeypatch):
    real = harness._PooledTap.__call__

    def skip_first(self, *args):    # as if batch 0's head went elsewhere
        if not getattr(self, "skipped", False):
            self.skipped = True
            return self.head(*args)
        return real(self, *args)

    monkeypatch.setattr(harness._PooledTap, "__call__", skip_first)
    with pytest.raises(RuntimeError, match="_head_jit 2 times, not 3"):
        run("qr-smoke")


def test_program_that_bypasses_the_seam_fails(monkeypatch):
    from repro.data import synthetic
    from repro.launch import serve_rec

    # a copy of the generator module that the harness does not patch
    monkeypatch.setattr(serve_rec, "synthetic",
                        types.SimpleNamespace(**vars(synthetic)))
    with pytest.raises(RuntimeError, match="drew 0 batches"):
        run("qr-smoke")


def test_new_traffic_mix_and_metric_are_found_by_name(tmp_path):
    traffic = tmp_path / "traffic"
    metrics = tmp_path / "metrics"
    shutil.copytree(DATA / "traffic", traffic)
    shutil.copytree(harness.BENCH / "metrics", metrics)
    (traffic / "throwaway.json").write_text(json.dumps(
        {"mode": "overlap", "batch": 8, "alpha": 0.5, "warmup_batches": 2}))
    (metrics / "throwaway_batches.py").write_text(
        "def read(ctx):\n    return float(ctx.window_batches)\n")
    (tmp_path / "limits").mkdir()
    (tmp_path / "limits" / "qr-new.json").write_text(
        (DATA / "limits" / "qr-smoke.json").read_text())
    spec = smoke_spec([("qr-new", ("dlrm-qr-smoke", "throwaway"))])
    spec["end_to_end"].append({"name": "throwaway_batches", "unit": "batches",
                               "better": "higher", "bound": 0.25,
                               "source": "host_clock"})
    dirs = harness.Dirs(configs=DATA / "configs", traffic=traffic,
                        metrics=metrics, limits=tmp_path / "limits")
    line = run("qr-new", spec=spec, dirs=dirs)
    assert line["correct"] is True
    assert line["metrics"]["throwaway_batches"]["value"] == 2.0
    assert line["attempted"] == 8 * 2


def test_traced_run_on_cpu_reads_host_spans_and_no_device_numbers():
    line = run("tt-smoke", trace=True)
    assert line["correct"] is True
    assert line["metrics"]["host_prep_ms.lat"]["value"] > 0
    assert line["metrics"]["cache_hit_rate.lat"]["value"] > 0
    for name in ("gather_ms.lat", "idle_share.lat", "gather_roofline.lat",
                 "step_mfu.lat"):
        assert name not in line["metrics"]           # no TPU plane, no value
    assert "busy_s" not in line["device"]


def test_cli_without_a_tpu_prints_no_result(capsys):
    rc = run_mod.main(["--workload", "qr-zipf-2k", "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    assert rc == 3
    out, err = capsys.readouterr()
    assert out == "" and "TPU" in err


def test_cli_in_a_checkout_without_the_program_fails(tmp_path):
    import subprocess

    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qr-zipf-2k", "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0 and out.stdout == ""
