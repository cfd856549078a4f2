import os
os.environ["XLA_FLAGS"] = os.environ.get(
    "REPRO_XLA_FLAGS", "--xla_force_host_platform_device_count=512"
)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: builds the
production mesh from 512 placeholder host devices, lowers the real
train/prefill/decode step with full-size ShapeDtypeStruct inputs (no
allocation), compiles it, and records:

* ``memory_analysis()``  — per-device bytes (proves the cell fits HBM),
* ``cost_analysis()``    — HLO FLOPs/bytes for the roofline terms,
* collective bytes       — parsed from the post-SPMD HLO text, per op kind,
* analytic MODEL_FLOPS   — 6·N·D (dense) / 6·N_active·D (MoE).

One JSON per cell lands in ``experiments/dryrun/<mesh>/`` for
``benchmarks/roofline.py`` to consume.

Usage:
    python -m repro.launch.dryrun --arch qwen2-1.5b --shape train_4k --mesh pod1
    python -m repro.launch.dryrun --all --mesh both
    python -m repro.launch.dryrun --list
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import registry
from repro.configs.base import LM_SHAPES, ModelConfig, ShapeConfig
from repro.distributed import sharding as SH
from repro.launch import hlo_analysis
from repro.launch import mesh as mesh_mod
from repro.train import optimizer as opt_mod
from repro.train.serve_step import serve_family
from repro.train.train_step import make_train_step

SHAPES = {s.name: s for s in LM_SHAPES}


def param_counts(params_sds, cfg: ModelConfig) -> dict:
    """Total + MoE-active parameter counts from the abstract tree."""
    total = 0
    moe_total = 0
    for path, leaf in jax.tree.flatten_with_path(params_sds)[0]:
        n = 1
        for d in leaf.shape:
            n *= d
        total += n
        keys = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        if "moe" in keys and "router" not in keys:
            moe_total += n
    active = total
    if cfg.num_experts and cfg.top_k:
        active = total - moe_total + moe_total * cfg.top_k / cfg.num_experts
    return {"total": int(total), "active": int(active)}


def model_flops(counts: dict, shape: ShapeConfig) -> float:
    """6·N·D with D = tokens processed by the lowered step."""
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2 * counts["active"] * tokens          # fwd only
    else:  # decode: one token per sequence
        tokens = shape.global_batch
        return 2 * counts["active"] * tokens
    return 6 * counts["active"] * tokens


# ---------------------------------------------------------------------------
# per-cell lowering
# ---------------------------------------------------------------------------

def _batch_shardings(batch_sds: dict, mesh, act_rules) -> dict:
    out = {}
    for k, v in batch_sds.items():
        axes = ("batch",) + (None,) * (len(v.shape) - 1)
        out[k] = NamedSharding(mesh, SH.resolve_spec(mesh, v.shape, axes, act_rules))
    return out


def _replicated_tree(tree, mesh):
    return jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)


def lower_cell(
    arch_id: str,
    shape_name: str,
    *,
    multi_pod: bool,
    embedding_kind: str | None = None,
    qr_collision: int | None = None,
    microbatches: int = 8,
    seq_parallel: bool = False,
    serve_params: bool = False,
    extra_cfg: dict | None = None,
) -> dict:
    binding = registry.get(arch_id)
    cfg = binding.config
    if embedding_kind:
        cfg = cfg.replace(embedding_kind=embedding_kind)
    if qr_collision:
        cfg = cfg.replace(qr_collision=qr_collision)
    if extra_cfg:
        cfg = cfg.replace(**extra_cfg)
    shape = SHAPES[shape_name]
    status = registry.shape_status(binding, shape)
    rec = {
        "arch": arch_id,
        "shape": shape_name,
        "mesh": "pod2" if multi_pod else "pod1",
        "kind": shape.kind,
        "embedding": cfg.embedding_kind,
        "variant": dict(extra_cfg or {}, serve_params=serve_params),
        "status": status,
    }
    if status != "run":
        return rec

    mesh = mesh_mod.make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    act_rules = SH.multi_pod_rules() if multi_pod else dict(SH.DEFAULT_RULES)
    par_rules = SH.multi_pod_param_rules() if multi_pod else dict(SH.PARAM_RULES)
    if serve_params:
        # inference placement: parameters bf16, TP-sharded only (no FSDP over
        # `data` -> no per-layer weight all-gathers in the decode loop)
        cfg = cfg.replace(param_dtype="bfloat16")
        par_rules["embed"] = None
        rec_extra = {"serve_params": True}
    else:
        rec_extra = {}
    if seq_parallel:
        act_rules["seq"] = ("model",)
    dp = mesh.shape["data"] * mesh.shape.get("pod", 1)
    if shape.global_batch % dp:
        act_rules["batch"] = None                     # B=1 long-context cells

    t0 = time.time()
    params_sds, axes = registry.abstract_params(binding, cfg)
    pshard = SH.shardings_for_tree(mesh, params_sds, axes, par_rules)
    counts = param_counts(params_sds, cfg)
    rec["params_total"] = counts["total"]
    rec["params_active"] = counts["active"]
    rec["model_flops"] = model_flops(counts, shape)
    rec["abstract_s"] = round(time.time() - t0, 2)

    mb = microbatches if shape.kind == "train" else 1
    while shape.global_batch % max(mb, 1) or (shape.global_batch // max(mb, 1)) % dp:
        mb //= 2
        if mb <= 1:
            mb = 1
            break
    rec["microbatches"] = mb

    if shape.kind == "train":
        batch_sds = registry.batch_specs(binding, cfg, shape.global_batch, shape.seq_len)
        bshard = _batch_shardings(batch_sds, mesh, act_rules)
        opt_sds = jax.eval_shape(opt_mod.init, params_sds)
        opt_shard = {
            "mu": pshard, "nu": pshard, "step": NamedSharding(mesh, P()),
        }
        loss0 = registry.train_loss_fn(binding, cfg)

        def loss_fn(params, batch):
            with SH.use_rules(mesh, act_rules):
                return loss0(params, batch)

        step = make_train_step(loss_fn, opt_mod.OptConfig(), microbatches=mb)
        fn = jax.jit(
            step,
            in_shardings=(pshard, opt_shard, bshard),
            out_shardings=(pshard, opt_shard, None),
        )
        args = (params_sds, opt_sds, batch_sds)
    elif shape.kind == "prefill":
        fam = serve_family(binding.kind)
        batch_sds = registry.batch_specs(binding, cfg, shape.global_batch, shape.seq_len)
        bshard = _batch_shardings(batch_sds, mesh, act_rules)
        cache_sds = registry.cache_specs(binding, cfg, shape.global_batch, shape.seq_len)
        ca = fam.cache_axes()
        cshard = (
            SH.shardings_for_tree(mesh, cache_sds, ca, act_rules)
            if ca is not None
            else _replicated_tree(cache_sds, mesh)
        )

        def fn_prefill(params, batch):
            with SH.use_rules(mesh, act_rules):
                return fam.prefill(params, batch, cfg, shape.seq_len)

        fn = jax.jit(
            fn_prefill,
            in_shardings=(pshard, bshard),
            out_shardings=(None, cshard),
        )
        args = (params_sds, batch_sds)
    else:  # decode
        fam = serve_family(binding.kind)
        cache_sds = registry.cache_specs(binding, cfg, shape.global_batch, shape.seq_len)
        ca = fam.cache_axes()
        cshard = (
            SH.shardings_for_tree(mesh, cache_sds, ca, act_rules)
            if ca is not None
            else _replicated_tree(cache_sds, mesh)
        )
        token_sds = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
        tshard = NamedSharding(
            mesh,
            SH.resolve_spec(mesh, token_sds.shape, ("batch", None), act_rules),
        )
        pos_sds = jax.ShapeDtypeStruct((), jnp.int32)

        def fn_decode(params, cache, token, pos):
            with SH.use_rules(mesh, act_rules):
                return fam.decode(params, cache, token, pos, cfg)

        fn = jax.jit(
            fn_decode,
            in_shardings=(pshard, cshard, tshard, NamedSharding(mesh, P())),
            out_shardings=(None, cshard),
        )
        args = (params_sds, cache_sds, token_sds, pos_sds)

    t0 = time.time()
    lowered = fn.lower(*args)
    rec["lower_s"] = round(time.time() - t0, 2)

    try:
        lc = lowered.cost_analysis()
        rec["lowered_cost"] = {
            "flops": lc.get("flops", 0.0),
            "bytes_accessed": lc.get("bytes accessed", 0.0),
        }
    except Exception:
        rec["lowered_cost"] = None

    t0 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t0, 2)

    ma = compiled.memory_analysis()
    rec["memory"] = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "peak_est_bytes": int(
            ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes
        ),
    }
    ca_ = compiled.cost_analysis()
    rec["compiled_cost"] = {
        "flops": float(ca_.get("flops", 0.0)) if ca_ else 0.0,
        "bytes_accessed": float(ca_.get("bytes accessed", 0.0)) if ca_ else 0.0,
    }
    hlo = compiled.as_text()
    rec["hlo_bytes_len"] = len(hlo)
    t0 = time.time()
    rec["hlo"] = hlo_analysis.analyze(hlo)   # loop-aware per-device FLOPs/bytes
    rec["analyze_s"] = round(time.time() - t0, 2)
    rec["chips"] = chips
    rec["_hlo_text"] = hlo                   # stripped to .hlo.gz by run_cells
    return rec


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def run_cells(cells, out_dir: str, *, force: bool = False, tag: str | None = None,
              **kw) -> list[dict]:
    results = []
    for arch_id, shape_name, multi_pod in cells:
        mesh_tag = "pod2" if multi_pod else "pod1"
        base = tag or kw.get("embedding_kind") or "config"
        sp = "-sp" if kw.get("seq_parallel") else ""
        path = os.path.join(
            out_dir, mesh_tag, f"{arch_id}__{shape_name}__{base}{sp}.json"
        )
        os.makedirs(os.path.dirname(path), exist_ok=True)
        if os.path.exists(path) and not force:
            with open(path) as f:
                results.append(json.load(f))
            print(f"[skip] {path}")
            continue
        print(f"[dryrun] {arch_id} x {shape_name} x {mesh_tag} ({base}{sp}) ...",
              flush=True)
        t0 = time.time()
        try:
            rec = lower_cell(arch_id, shape_name, multi_pod=multi_pod, **kw)
        except Exception as e:  # record failures — they are bugs to fix
            rec = {
                "arch": arch_id, "shape": shape_name, "mesh": mesh_tag,
                "status": f"error: {type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-4000:],
            }
        rec["wall_s"] = round(time.time() - t0, 2)
        hlo_text = rec.pop("_hlo_text", None)
        if hlo_text is not None:
            import gzip

            with gzip.open(path.replace(".json", ".hlo.gz"), "wt") as g:
                g.write(hlo_text)
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        print(f"   -> {rec.get('status')} ({rec['wall_s']}s)", flush=True)
        results.append(rec)
    return results


def reanalyze(out_dir: str) -> None:
    """Refresh every record's 'hlo' section from the saved .hlo.gz (no
    recompilation) — used when the analyzer's cost model improves."""
    import glob
    import gzip

    for path in sorted(glob.glob(os.path.join(out_dir, "*", "*.json"))):
        gz = path.replace(".json", ".hlo.gz")
        if not os.path.exists(gz):
            continue
        with open(path) as f:
            rec = json.load(f)
        with gzip.open(gz, "rt") as g:
            rec["hlo"] = hlo_analysis.analyze(g.read())
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        print(f"[reanalyzed] {path}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="pod1", choices=["pod1", "pod2", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--reanalyze", action="store_true")
    ap.add_argument("--embedding", default=None, choices=[None, "dense", "hashed", "qr"])
    ap.add_argument("--collision", type=int, default=None)
    ap.add_argument("--microbatches", type=int, default=8)
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--qr-head", default=None, choices=[None, "factorized", "materialize"])
    ap.add_argument("--embedding-exec", default=None, choices=[None, "gspmd", "twolevel"])
    ap.add_argument("--moe-dispatch", default=None, choices=[None, "scatter", "gather"])
    ap.add_argument("--remat-policy", default=None, choices=[None, "full", "dots"])
    ap.add_argument("--flash-block-dtype", default=None, choices=[None, "f32", "bf16"])
    ap.add_argument("--serve-params", action="store_true",
                    help="inference placement: bf16 params, TP-only (no FSDP)")
    ap.add_argument("--tag", default=None, help="output filename variant tag")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    if args.reanalyze:
        reanalyze(args.out)
        return

    if args.list:
        for b, s, status in registry.cells(include_skipped=True):
            print(f"{b.arch_id:24s} {s.name:12s} {status}")
        return

    meshes = {"pod1": [False], "pod2": [True], "both": [False, True]}[args.mesh]
    if args.all:
        cells = [
            (b.arch_id, s.name, mp)
            for mp in meshes
            for b, s, _ in registry.cells()
        ]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        cells = [(args.arch, args.shape, mp) for mp in meshes]

    extra_cfg = {}
    if args.qr_head:
        extra_cfg["qr_head"] = args.qr_head
    if args.embedding_exec:
        extra_cfg["embedding_exec"] = args.embedding_exec
    if args.moe_dispatch:
        extra_cfg["moe_dispatch"] = args.moe_dispatch
    if args.remat_policy:
        extra_cfg["remat_policy"] = args.remat_policy
    if args.flash_block_dtype:
        extra_cfg["flash_block_dtype"] = args.flash_block_dtype
    results = run_cells(
        cells, args.out, force=args.force, tag=args.tag,
        embedding_kind=args.embedding, qr_collision=args.collision,
        microbatches=args.microbatches, seq_parallel=args.seq_parallel,
        extra_cfg=extra_cfg or None, serve_params=args.serve_params,
    )
    ok = sum(1 for r in results if r.get("status") == "run")
    print(f"\n{ok}/{len(results)} cells compiled clean")
    bad = [r for r in results if str(r.get("status", "")).startswith("error")]
    for r in bad:
        print(f"FAILED: {r['arch']} x {r['shape']} x {r['mesh']}: {r['status']}")


if __name__ == "__main__":
    main()
