"""The benchmark's command: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on the machine that holds the chips.  The
last line of standard output is the result (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``, then
``checks``: each number compared beside its limit).  The run's own numbers
go to standard error, the compared ones last.  Exits non-zero and prints no
result when JAX finds no TPU or fewer chips than the cell asks for, and when
the program's ``src/`` is not beside ``bench/``.
"""

from __future__ import annotations

import time

START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def process_start() -> float:
    """Wall-clock start of this process (interpreter start-up included)."""
    try:
        import psutil
    except ImportError:
        return START
    return psutil.Process().create_time()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the program (src/repro) is not under {ROOT}",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    import jax

    from bench import harness
    from repro.launch import compile_cache

    try:
        cell = harness.prepare(args.workload)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    compile_cache.enable()
    # every compile goes to the persistent cache, however short
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    line = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                            process_start=process_start())
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
