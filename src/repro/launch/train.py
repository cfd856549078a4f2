"""End-to-end training driver: config -> mesh -> train loop, fault-tolerant.

Production behaviors implemented and exercised here (CPU smoke scale):

* auto-resume from the newest atomic checkpoint (params + optimizer + data
  cursor) — `--ckpt-dir`;
* preemption safety: SIGTERM/SIGINT checkpoints synchronously then exits 0
  (the behavior a k8s/Borg eviction expects);
* deterministic data: batch = f(seed, step) so restarts replay identically;
* optional elastic restart onto a different mesh shape (`--mesh-shape`),
  using the mesh-agnostic checkpoint format.

Usage (CPU smoke):
    PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --smoke \
        --steps 20 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
    PYTHONPATH=src python -m repro.launch.train --arch dlrm-qr --smoke \
        --steps 10 --batch 16   # the paper's model; GnR via repro.engine
"""

from __future__ import annotations

import argparse
import dataclasses
import signal
import sys
import time

import jax

from repro.checkpoint import checkpointer as ckpt
from repro.configs import registry
from repro.data import synthetic
from repro.distributed import sharding as SH
from repro.launch import compile_cache
from repro.launch import mesh as mesh_mod
from repro.train import optimizer as opt_mod
from repro.train.train_step import make_dlrm_loss, make_train_step


def _build_model(args):
    """-> (cfg, params, axes, loss_fn0, make_batch) for LM or DLRM archs.

    DLRM archs (``--arch dlrm-qr`` etc.) train the paper's model: the
    embedding layer routes through the engine front door (``repro.engine``,
    via ``dlrm.forward_dlrm``) and batches carry planted CTR structure so the
    loss is learnable.
    """
    if args.arch.startswith("dlrm"):
        from repro import engine as engine_mod
        from repro.engine import EngineSpec
        from repro.models import dlrm as dlrm_mod

        name = f"{args.arch}-smoke" if args.smoke else args.arch
        cfg = registry.get_dlrm(name)
        if args.embedding:
            cfg = dataclasses.replace(cfg, embedding_kind=args.embedding)
        params, axes = dlrm_mod.init_dlrm(jax.random.PRNGKey(args.seed), cfg)
        loss_fn0 = make_dlrm_loss(cfg)
        truth = synthetic.dlrm_truth(cfg)
        eng = engine_mod.engine_for(EngineSpec.from_dlrm(cfg))
        print(f"[engine] {cfg.name}: {eng.summary()}")

        def make_batch(b, s, **kw):
            return synthetic.dlrm_planted_batch(cfg, truth, b, **kw)

        return cfg, params, axes, loss_fn0, make_batch

    binding = registry.get(args.arch)
    cfg = binding.smoke if args.smoke else binding.config
    if args.embedding:
        cfg = cfg.replace(embedding_kind=args.embedding)
    init = registry.init_fn(binding)
    params, axes = init(jax.random.PRNGKey(args.seed), cfg)
    loss_fn0 = registry.train_loss_fn(binding, cfg)
    make_batch = registry.make_batch_fn(binding, cfg)
    return cfg, params, axes, loss_fn0, make_batch


def build(args):
    cfg, params, axes, loss_fn0, make_batch = _build_model(args)
    opt_state = opt_mod.init(params)

    mesh = None
    if args.mesh_shape:
        shape = tuple(int(x) for x in args.mesh_shape.split(","))
        names = ("data", "model")[: len(shape)] if len(shape) <= 2 else (
            "pod", "data", "model"
        )
        mesh = mesh_mod.make_mesh(shape, names)
        rules = dict(SH.DEFAULT_RULES)
        pshard = SH.shardings_for_tree(mesh, params, axes, SH.PARAM_RULES)
        params = jax.device_put(params, pshard)
        opt_state = {
            "mu": jax.device_put(opt_state["mu"], pshard),
            "nu": jax.device_put(opt_state["nu"], pshard),
            "step": opt_state["step"],
        }
    else:
        rules = None

    def loss_fn(p, batch):
        with SH.use_rules(mesh, rules):
            return loss_fn0(p, batch)

    opt_cfg = opt_mod.OptConfig(
        lr=args.lr, warmup_steps=min(100, args.steps // 10 + 1),
        total_steps=args.steps,
    )
    step_fn = jax.jit(
        make_train_step(loss_fn, opt_cfg, microbatches=args.microbatches)
    )
    return cfg, params, opt_state, step_fn, make_batch


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--embedding", default=None,
                    choices=[None, "dense", "hashed", "qr", "tt"])
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--mesh-shape", default=None, help="e.g. 2,4 for (data,model)")
    ap.add_argument("--log-every", type=int, default=5)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    compile_cache.enable()
    cfg, params, opt_state, step_fn, make_batch = build(args)
    pipe = synthetic.Pipeline(
        make_batch=lambda seed, step: make_batch(args.batch, args.seq, seed=seed, step=step),
        seed=args.seed,
    )

    start = 0
    if args.ckpt_dir:
        latest = ckpt.latest_step(args.ckpt_dir)
        if latest is not None:
            state = {"params": params, "opt": opt_state}
            state, extra = ckpt.restore(args.ckpt_dir, latest, state)
            params, opt_state = state["params"], state["opt"]
            pipe.seek(extra["pipeline"])
            start = latest
            print(f"[resume] step {start} from {args.ckpt_dir}")

    stop = {"now": False}

    def _graceful(signum, frame):
        stop["now"] = True

    signal.signal(signal.SIGTERM, _graceful)
    signal.signal(signal.SIGINT, _graceful)

    def save(step):
        if args.ckpt_dir:
            ckpt.save(
                args.ckpt_dir, step, {"params": params, "opt": opt_state},
                extra={"pipeline": pipe.state(), "arch": args.arch},
            )
            ckpt.prune(args.ckpt_dir, keep=3)

    t_last = time.time()
    for step in range(start, args.steps):
        batch = next(pipe)
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        if (step + 1) % args.log_every == 0 or step == start:
            dt = time.time() - t_last
            t_last = time.time()
            print(
                f"step {step + 1:5d} loss {float(metrics['loss']):.4f} "
                f"lr {float(metrics['lr']):.2e} gnorm {float(metrics['grad_norm']):.3f} "
                f"({dt:.2f}s)", flush=True,
            )
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            save(step + 1)
        if stop["now"]:
            print(f"[preempt] checkpointing at step {step + 1} and exiting")
            save(step + 1)
            return 0
    save(args.steps)
    print("done")
    return 0


if __name__ == "__main__":
    sys.exit(main())
