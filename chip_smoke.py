"""Bring-up smoke test: the DLRM serving path on a TPU at published widths.

One process, the chip JAX finds, the entry points a user calls.  With no
arguments (one chip):

  (a) ``dlrm-qr`` — 26 tables x 2M rows x dim 128, pooling 32: the offline
      plan (``serve_rec.build_serve_state``), then 4 batches of 2048 served
      by ``serve_rec.run_pipeline`` in sequential and in overlap mode;
  (b) the same for ``dlrm-tt`` (the fused TT megakernel);
  (c) for one batch of each, the pooled output of ``serve_gather`` against
      the float32 jnp oracle (``kernels.ref.packed_*_ref`` at HIGHEST matmul
      precision), and overlap-mode logits identical to sequential-mode ones;
  (d) the lowered ``serve_gather`` program holds the Pallas kernel
      (``tpu_custom_call``), so no reference or interpret path passes;
  (e) 3 training steps of ``dlrm-qr`` at batch 2048 through
      ``launch/train.py``'s ``build``; the loss must stay finite.

Tables row-sharded over four chips are served by the normal path
(``serve_rec --chips 4``) and measured by the benchmark's
``dense-zipf-2k-4chip`` cell.

Every phase prints its numbers on its own lines.  The last line is
``{"ok": true, "device": {...}}``, printed only after every phase passed.
Exits non-zero without it when JAX finds no TPU, when the repository's
``src/`` is not beside this file, or when any phase fails.

Usage:
    python chip_smoke.py
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
SEED = 0
ALPHA = 1.05
BATCH = 2048                # the serve_2k request batch (configs/base.py)
BATCHES = 4                 # batch 0 compiles; 1..3 are the steady window
TRAIN_BATCH = 2048
TRAIN_STEPS = 3
# Samples compared with the oracle.  The TT oracle gathers one 8 KiB middle-
# core row per access (x3 with the cache and the select): 128 samples are
# 106,496 accesses, under 3 GB; the QR oracle takes the whole batch.
ORACLE_SAMPLES = {"dlrm-qr": BATCH, "dlrm-tt": 128}
TOL = 1e-5                  # rtol = atol: f32 summation-order differences


def log(msg: str) -> None:
    print(msg, flush=True)


def peak_bytes() -> int | None:
    import jax

    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


# ---------------------------------------------------------------------------
# (a) / (b): serve at published widths through the serving entry points
# ---------------------------------------------------------------------------

def serve(arch: str, *, batch: int = BATCH, batches: int = BATCHES):
    import jax
    import numpy as np

    from repro.configs import registry
    from repro.launch import serve_rec
    from repro.models import dlrm

    cfg = registry.get_dlrm(arch)
    t0 = time.perf_counter()
    state = serve_rec.build_serve_state(cfg, shards=4, alpha=ALPHA, seed=SEED)
    params, _ = dlrm.init_dlrm(jax.random.PRNGKey(SEED), cfg)
    jax.block_until_ready(params)
    log(f"[{arch}] plan + init {time.perf_counter() - t0:.3f}s: "
        f"{cfg.num_tables} tables x {cfg.vocab_per_table} rows x dim {cfg.dim}, "
        f"pooling {cfg.pooling}, packed rows {state.layout.total_rows}, "
        f"cache slots {state.layout.total_slots}")
    logits = {}
    for mode in ("sequential", "overlap"):
        res = serve_rec.run_pipeline(
            cfg, batch=batch, batches=batches, alpha=ALPHA, seed=SEED,
            mode=mode, state=state, params=params,
        )
        log(f"[{arch}/{mode}] compile_s={res['compile_s']:.6f} "
            f"batch_s={[round(x, 6) for x in res['latencies_s']]} "
            f"hit_rate={res['hit_rate']:.6f} peak_bytes_in_use={peak_bytes()}")
        logits[mode] = [np.asarray(x) for x in res["logits"]]
        check(all(np.isfinite(x).all() for x in logits[mode]),
              f"{arch}/{mode}: non-finite logits")
    same = all(np.array_equal(a, b)
               for a, b in zip(logits["sequential"], logits["overlap"]))
    log(f"[{arch}] overlap logits == sequential logits: {same}")
    check(same, f"{arch}: overlap and sequential logits differ")
    return cfg, state, params


# ---------------------------------------------------------------------------
# (c) / (d): on-chip pooled output vs the f32 oracle; the kernel is there
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _oracle_fn(layout):
    """The jitted f32 oracle for one packed layout: the serving step's math
    (index packing, slot routing, cache staging, combiner scale) on the flat
    buffers through ``kernels.ref`` — no kernel code."""
    import jax

    from repro.core import packed_tables as PT
    from repro.kernels import ref

    @jax.jit
    def oracle(packed, idx, slot, cache_rows, scale):
        flat = {k: v.reshape(v.shape[0], -1) for k, v in packed.items()}
        streams = PT.pack_indices(idx, layout)
        gslot = PT.global_slots(slot, layout)
        big = flat[PT.big_key(layout.kind)]
        cache = big[cache_rows]
        if layout.kind == "qr":
            out = ref.packed_qr_bag_ref(big, cache, flat["r"], streams["q_idx"],
                                        gslot, streams["r_idx"])
        elif layout.kind == "tt":
            out = ref.packed_tt_bag_ref(
                flat["g1"], big, flat["g3"], cache, streams["i1"],
                streams["i2"], streams["i3"], gslot, dims=layout.tt_dims,
            )
        else:
            out = ref.packed_bag_ref(big, cache, streams["idx"], gslot)
        return out * scale[None, :, None]

    return oracle


def verify_gather(arch: str, cfg, state, params, *, samples: int,
                  batch: int = BATCH, want_kernel: bool = True):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import packed_tables as PT
    from repro.data import synthetic
    from repro.engine import big_rows

    eng, layout = state.engine, state.layout
    packed = eng.pack(params["tables"])
    scheds = state.fresh_schedulers()
    idx = np.asarray(synthetic.dlrm_batch(cfg, batch, seed=SEED, step=0,
                                          alpha=ALPHA)["idx"])
    emb = state.bags[0].emb
    rows = [big_rows(idx[:, t], emb) for t in range(cfg.num_tables)]
    for s, r in zip(scheds, rows):
        s.prefetch(r)
    slot = np.stack([s.slots_for(r) for s, r in zip(scheds, rows)], axis=1)
    args = (jnp.asarray(idx), jnp.asarray(slot),
            jnp.asarray(eng.packed_cache_rows(scheds)))

    if want_kernel:                                     # (d)
        text = jax.jit(eng.serve_gather).lower(packed, *args).as_text()
        has = "tpu_custom_call" in text
        log(f"[{arch}] lowered serve_gather holds tpu_custom_call: {has}")
        check(has, f"{arch}: serve_gather lowered without the Pallas kernel")

    t0 = time.perf_counter()
    pooled = jax.block_until_ready(eng.serve_gather(packed, *args))
    gather_s = time.perf_counter() - t0
    scale = PT.combiner_scale(state.bags, jnp.float32)
    n = min(samples, batch)
    with jax.default_matmul_precision("highest"):
        want = _oracle_fn(layout)(packed, args[0][:n], args[1][:n], args[2], scale)
    got, want = np.asarray(pooled[:n], np.float32), np.asarray(want, np.float32)
    err = float(np.max(np.abs(got - want)))
    ok = bool(np.allclose(got, want, rtol=TOL, atol=TOL))
    log(f"[{arch}] serve_gather vs f32 oracle on {n} samples: "
        f"max_abs_diff={err:.9g} (rtol=atol={TOL}) ok={ok}; "
        f"hit share {float((slot >= 0).mean()):.6f}; gather_s={gather_s:.6f}")
    check(ok, f"{arch}: serve_gather disagrees with the f32 oracle ({err})")


# ---------------------------------------------------------------------------
# (e): a few training steps through the training entry point
# ---------------------------------------------------------------------------

def train(arch: str = "dlrm-qr", *, batch: int = TRAIN_BATCH,
          steps: int = TRAIN_STEPS, smoke: bool = False):
    import numpy as np

    from repro.launch import train as train_mod

    argv = ["--arch", arch, "--steps", str(steps), "--batch", str(batch)]
    args = train_mod.parse_args(argv + (["--smoke"] if smoke else []))
    cfg, params, opt_state, step_fn, make_batch = train_mod.build(args)
    for step in range(steps):
        batch_ = make_batch(args.batch, args.seq, seed=args.seed, step=step)
        t0 = time.perf_counter()
        params, opt_state, metrics = step_fn(params, opt_state, batch_)
        loss = float(metrics["loss"])
        log(f"[train/{cfg.name}] step {step + 1} batch {batch} loss {loss:.6f} "
            f"({time.perf_counter() - t0:.6f}s) peak_bytes_in_use={peak_bytes()}")
        check(bool(np.isfinite(loss)), f"train/{cfg.name}: loss {loss} at step {step + 1}")


def one_chip() -> None:
    for arch in ("dlrm-qr", "dlrm-tt"):
        cfg, state, params = serve(arch)
        verify_gather(arch, cfg, state, params, samples=ORACLE_SAMPLES[arch])
        del state, params
    train("dlrm-qr")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); nothing run",
              file=sys.stderr)
        return 2
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: {SRC} holds no repro package; nothing run", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.launch import compile_cache

    log(f"# device {devices[0].device_kind} x{len(devices)}, "
        f"compile cache {compile_cache.enable()}")
    t0 = time.perf_counter()
    one_chip()
    log(f"# all phases passed in {time.perf_counter() - t0:.3f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
