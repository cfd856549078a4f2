"""Launcher-level integration: train loop with checkpoint/auto-resume,
serving driver, dry-run cell listing."""

import os

import pytest

from repro.checkpoint import checkpointer as ckpt


@pytest.mark.slow
def test_train_resume_roundtrip(tmp_path, capsys):
    from repro.launch.train import main

    args = [
        "--arch", "qwen2-1.5b", "--smoke", "--steps", "6", "--batch", "4",
        "--seq", "32", "--ckpt-dir", str(tmp_path), "--ckpt-every", "2",
        "--log-every", "2",
    ]
    assert main(args) == 0
    assert ckpt.latest_step(str(tmp_path)) == 6

    # resume: a second invocation starts from step 6 and does nothing more
    assert main(args) == 0
    out = capsys.readouterr().out
    assert "[resume] step 6" in out


@pytest.mark.slow
def test_train_elastic_mesh_restart(tmp_path, mesh_runner):
    """Train on a (2,2) mesh, checkpoint, resume onto (4,1) — the elastic
    re-mesh path end-to-end (subprocess owns its device count)."""
    mesh_runner(
        f"""
from repro.launch.train import main
args = ["--arch", "qwen2-1.5b", "--smoke", "--steps", "4", "--batch", "4",
        "--seq", "32", "--ckpt-dir", r"{tmp_path}", "--ckpt-every", "2",
        "--mesh-shape", "2,2"]
assert main(args) == 0
args[-1] = "4,1"
args[4] = "8"   # --steps 8: continue on the new mesh
assert main(args) == 0
print("OK")
""",
        n_devices=4,
        timeout=560,
    )


@pytest.mark.slow
def test_serve_driver(capsys):
    from repro.launch.serve import main

    rc = main([
        "--arch", "xlstm-125m", "--smoke", "--batch", "2",
        "--prompt-len", "8", "--max-new", "4",
    ])
    assert rc == 0
    assert "generated (2, 4)" in capsys.readouterr().out


def test_dryrun_list(capsys):
    import subprocess
    import sys

    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["REPRO_XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--list"],
        capture_output=True, text=True, env=env, timeout=120,
    )
    assert out.returncode == 0
    lines = [l for l in out.stdout.splitlines() if l.strip()]
    assert len(lines) == 40                      # the full assigned grid
    assert sum("run" in l for l in lines) == 32


def test_compile_cache_placement(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and nothing else is set; otherwise the
    cache sits at one fixed path in the checkout."""
    from pathlib import Path

    from repro.launch import compile_cache

    calls = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache.enable()
    assert calls == [("jax_compilation_cache_dir", path)]
    assert path == compile_cache.enable()                  # stable across calls
    assert Path(path) == Path(__file__).resolve().parents[1] / ".jax_cache"


def test_peak_rates_keyed_by_device_kind():
    from repro.launch.mesh import V5E, peaks

    chip = peaks(V5E)
    assert (chip.flops_bf16, chip.hbm_bw, chip.hbm_bytes) == (197e12, 819e9, 16 * 2**30)
    with pytest.raises(KeyError, match="'cpu'"):
        peaks("cpu")
