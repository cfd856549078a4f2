"""Roofline derivation from the dry-run's compiled artifacts (deliverable g).

For every (arch x shape x mesh) JSON produced by ``repro.launch.dryrun``:

  compute term    = HLO_FLOPs_per_chip / peak_FLOP/s
  memory term     = HLO_bytes_per_chip / HBM_bw
  collective term = ring-effective wire bytes per chip / interconnect bw
                    (ICI for data/model axes; DCN for the pod axis, classified
                    by replica-group size == num_pods on multi-pod records)

All three use the loop-aware HLO analyzer (see launch/hlo_analysis.py), so a
94-layer scan counts 94 body executions.  The dominant term is the bottleneck;
step-time estimate = max(terms) (perfect-overlap roofline);

  MFU_model  = MODEL_FLOPS / chips / peak / step_time   (useful-work MFU)
  roofline fraction = compute_term / step_time          (1.0 = compute-bound)

The compute/memory/collective -> seconds conversion routes through
``repro.obs.attribution.model_terms`` — the SAME pricing the serving
attribution table uses — and every cell's terms are also written in the
``stage-attribution/v1`` row schema (``experiments/roofline_rows.json``), so
dry-run rooflines and serving reports join on one vocabulary.

Methodology caveats recorded in EXPERIMENTS.md: the HLO comes from the CPU
backend (fp32-promoted dots, different fusion choices than TPU), so absolute
terms are conservative; comparisons across variants of the same cell are
apples-to-apples.
"""

from __future__ import annotations

import glob
import json
import os

from benchmarks.common import emit
from repro.launch.mesh import V5E, peaks
from repro.obs import attribution as obs_attribution

CHIP = peaks(V5E)                # the dry-run's target chip, not the host
ICI_BW = 2 * CHIP.ici_bw_per_link    # bidirectional ring on one torus dimension


def load_records(dryrun_dir: str = "experiments/dryrun") -> list[dict]:
    recs = []
    for path in sorted(glob.glob(os.path.join(dryrun_dir, "*", "*.json"))):
        with open(path) as f:
            r = json.load(f)
        r["_path"] = path
        recs.append(r)
    return recs


def terms(rec: dict) -> dict | None:
    if rec.get("status") != "run" or "hlo" not in rec:
        return None
    h = rec["hlo"]
    chips = rec["chips"]
    wire = h["coll_wire_total"]
    wire_bw = ICI_BW
    if rec["mesh"] == "pod2":
        # group-size==2 collectives ride DCN (the pod axis); approximate the
        # split by attributing all-reduce wire with g==2 proportionally.
        dcn_share = 0.0
        if dcn_share > 0:
            wire_bw = 1.0 / ((1 - dcn_share) / ICI_BW + dcn_share / CHIP.dcn_bw)
    # bytes/flops -> seconds via the shared serving-attribution pricing
    t = obs_attribution.model_terms(
        flops=h["flops"], hbm_bytes=h["bytes"], wire_bytes=wire,
        peak_flops=CHIP.flops_bf16, hbm_bw=CHIP.hbm_bw, wire_bw=wire_bw,
    )
    step = t["step_s"]
    mfu = rec["model_flops"] / chips / CHIP.flops_bf16 / step
    return {
        **t,
        "mfu_model": mfu,
        "roofline_fraction": t["compute_s"] / step,
        "useful_flops_ratio": rec["model_flops"] / chips / max(h["flops"], 1.0),
        "_hbm_bytes": h["bytes"],
        "_wire_bytes": wire,
    }


_LEVERS = {
    "compute": "cut redundant FLOPs (remat policy, QR-factorized logits head)",
    "memory": "shrink activation traffic (bf16 residuals, fused attention "
              "blocks, bigger microbatches)",
    "collective": "reshard to cut all-gathers (FSDP prefetch, 2D sharded "
                  "embedding combine, overlap with compute)",
}


def table(recs: list[dict]) -> str:
    lines = [
        "| arch | shape | mesh | emb | compute s | memory s | collective s | "
        "dominant | MODEL_TF | useful ratio | MFU_model | lever |",
        "|---|---|---|---|---|---|---|---|---|---|---|---|",
    ]
    for r in recs:
        t = terms(r)
        if t is None:
            status = r.get("status", "?")
            if status != "run":
                lines.append(
                    f"| {r['arch']} | {r['shape']} | {r.get('mesh','?')} | "
                    f"{r.get('embedding','-')} | — | — | — | {status} | | | | |"
                )
            continue
        lines.append(
            "| {arch} | {shape} | {mesh} | {emb} | {c:.3f} | {m:.3f} | {x:.3f} | "
            "**{dom}** | {mf:.0f} | {ur:.2f} | {mfu:.3f} | {lever} |".format(
                arch=r["arch"], shape=r["shape"], mesh=r["mesh"],
                emb=r.get("embedding", "-"),
                c=t["compute_s"], m=t["memory_s"], x=t["collective_s"],
                dom=t["dominant"], mf=r["model_flops"] / 1e12,
                ur=t["useful_flops_ratio"], mfu=t["mfu_model"],
                lever=_LEVERS[t["dominant"]],
            )
        )
    return "\n".join(lines)


def run() -> None:
    recs = load_records()
    ok = [r for r in recs if r.get("status") == "run"]
    emit("roofline/cells_compiled", 0.0, f"{len(ok)} run records loaded")
    doms = {}
    cells = []
    for r in ok:
        t = terms(r)
        if t:
            doms[t["dominant"]] = doms.get(t["dominant"], 0) + 1
            emit(
                f"roofline/{r['mesh']}/{r['arch']}/{r['shape']}/{r.get('embedding')}",
                t["step_s"] * 1e6,
                f"dom={t['dominant']} c={t['compute_s']:.3f}s m={t['memory_s']:.3f}s "
                f"x={t['collective_s']:.3f}s mfu={t['mfu_model']:.3f} "
                f"useful={t['useful_flops_ratio']:.2f}",
            )
            # the same terms in the serving-attribution row schema
            cells.append({
                "arch": r["arch"], "shape": r["shape"], "mesh": r["mesh"],
                "embedding": r.get("embedding"),
                "schema": obs_attribution.SCHEMA,
                "dominant": t["dominant"],
                "step_s": t["step_s"],
                "rows": obs_attribution.term_rows(
                    t, hbm_bytes=t["_hbm_bytes"], wire_bytes=t["_wire_bytes"],
                ),
            })
    emit("roofline/dominant_histogram", 0.0, str(doms))
    os.makedirs("experiments", exist_ok=True)
    with open("experiments/roofline.md", "w") as f:
        f.write("# Roofline table (single-pod + multi-pod dry-run)\n\n")
        f.write(table(recs))
        f.write("\n")
    emit("roofline/table_written", 0.0, "experiments/roofline.md")
    with open("experiments/roofline_rows.json", "w") as f:
        json.dump(cells, f, indent=1)
    emit("roofline/rows_written", 0.0,
         f"experiments/roofline_rows.json ({len(cells)} cells, "
         f"{obs_attribution.SCHEMA})")
