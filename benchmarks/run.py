"""Benchmark entry point: ``PYTHONPATH=src python -m benchmarks.run``.

One module per paper table/figure family; each prints CSV rows
``name,us_per_call,derived``. ``--only`` selects a subset.
"""

from __future__ import annotations

import argparse
import sys
import time

from benchmarks import common
from repro.launch import compile_cache
from benchmarks import (
    autotune, cache_sim, collision_sweep, design_opt, locality, roofline,
    serve_qps, serve_storm, traffic, tt_sweep,
)

SUITES = {
    "traffic": traffic.run,            # paper: weight-sharing traffic table (QR + TT)
    "locality": locality.run,          # paper: Q/R + TT-core temporal locality
    "design_opt": design_opt.run,      # paper: design-optimization ladders
    "collision_sweep": collision_sweep.run,  # paper: shortcoming analyses
    "tt_sweep": tt_sweep.run,          # paper: TT rank/factorization trade-off
    "cache_sim": cache_sim.run,        # paper: SRAM cache + duplication sweep
    "cache_drift": cache_sim.run_drift,  # online adaptation: hot-set rotation
    "serve_qps": serve_qps.run,        # measured QPS: packed megakernel pipeline
    "serve_storm": serve_storm.run,    # resilient front end: flash crowds + chaos
    "roofline": roofline.run,          # deliverable (g)
    "autotune": autotune.run,          # cost-model fidelity + tuned-vs-heuristic
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated suite names")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write all emitted rows as JSON (perf trajectory)")
    ap.add_argument("--tiny", action="store_true",
                    help="shrunk configs for suites that support them (CI smoke)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for suites that take one (stamped into their "
                         "JSON rows so any row reproduces its run)")
    args = ap.parse_args()
    compile_cache.enable()
    names = args.only.split(",") if args.only else list(SUITES)
    print("name,us_per_call,derived")
    failed = []
    for n in names:
        t0 = time.time()
        try:
            import inspect

            fn = SUITES[n]
            sig = inspect.signature(fn).parameters
            kw = {}
            if args.tiny and "tiny" in sig:
                kw["tiny"] = True
            if "seed" in sig:
                kw["seed"] = args.seed
            fn(**kw)
            wall = time.time() - t0
            # wall-clock rides the emitted rows so --json tracks a MEASURED
            # perf trajectory across PRs, not just modeled traffic
            common.emit(f"run/{n}_wall", wall * 1e6, f"suite wall-clock {wall:.1f}s")
        except Exception as e:  # keep the harness going; failures are visible
            import traceback

            traceback.print_exc()
            print(f"{n}/SUITE_FAILED,0.00,{type(e).__name__}: {e}")
            failed.append(n)
    if args.json:
        common.write_json(args.json)
    if failed:  # every suite still ran, but CI must see the breakage
        print(f"# FAILED suites: {','.join(failed)}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
