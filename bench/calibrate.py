"""Readings that set the correctness limits, on the chip, in one process.

    python3 bench/calibrate.py --workload qr-zipf-2k --seeds 101,102,... \\
        [--seconds 10] [--control-seeds 4] [--out chiprun_out/cal-qr.jsonl]

For each seed it sets the cell up and serves one window as a run does, then
reads each number that ``harness.compare`` checks, for the program against
the float32 reference (a sound run: the lower readings), and on the first
``--control-seeds`` seeds for the two controls put in the program's place
(the upper readings): ``control`` (tables and pooled in bfloat16, matmul
inputs in float8) for the logits, ``bf16_tables`` (only the tables in
bfloat16) for the pooled embeddings.  A limit goes between the two.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
CONTROLS = ("control", "bf16_tables")


def control_checks(s, m, limits, precision: str) -> dict:
    """``harness.compare``'s numbers with the reference in ``precision`` put
    in the program's place for the window ``s`` served."""
    import numpy as np

    from bench import harness
    from bench.reference import dlrm as reference

    logits, pooled = [], {}
    for j, b in enumerate(s.window):
        lg, pl = reference.forward_with_pooled(s.params, b["dense"], b["idx"],
                                               m, precision)
        logits.append(np.asarray(lg))
        if j in s.pooled:
            pooled[j] = np.asarray(pl)
    return harness.compare(s.params, s.window, logits, pooled, m, limits)[0]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control-seeds", type=int, default=4)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    import jax

    from bench import harness
    from repro.launch import compile_cache

    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    c = harness.prepare(args.workload)
    seeds = [int(x) for x in args.seeds.split(",")]
    out = open(args.out, "a") if args.out else None
    for i, seed in enumerate(seeds):
        t0 = time.time()
        s = harness.serve(c, seed, args.seconds, False, process_start=t0)
        served = time.time()
        program, _ = harness.compare(s.params, s.window, s.logits, s.pooled,
                                     c.model, c.limits)
        rec = {"workload": args.workload, "seed": seed,
               **{f"program.{k}": v["value"] for k, v in program.items()}}
        if i < args.control_seeds:
            for precision in CONTROLS:
                got = control_checks(s, c.model, c.limits, precision)
                rec.update({f"{precision}.{k}": v["value"]
                            for k, v in got.items()})
        rec.update({"batches": len(s.window), "wall_s": s.result["wall_s"],
                    "setup_s": s.setup_s, "serve_s": served - t0,
                    "check_s": time.time() - served,
                    "hit_rate": s.result["hit_rate"],
                    "peak_bytes": s.peak_bytes})
        del s              # free this seed's weights before the next set-up
        gc.collect()
        print(json.dumps(rec), flush=True)
        if out:
            out.write(json.dumps(rec) + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
