#!/usr/bin/env python3
"""The on-chip benchmark: one run of one cell.

    python3 benchmarks/onchip/run.py --workload stream.short --seed 7 \
        --seconds 10 --trace 0

Run from the root of a checkout. Cells, metrics and bounds are in
``BENCHMARK.json``; each cell's configuration, traffic mix and limits are
files under ``benchmarks/onchip/`` found by name. The last line of standard
output is the result as one JSON object; the last lines of standard error
give each number compared with its limit. Without a TPU, or with fewer chips
than the cell asks for, the run prints no result and exits nonzero.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
# JAX's persistent compilation cache lives at one fixed place inside the
# checkout: the path is part of each entry's key
CACHE_DIR = ROOT / ".jax_cache"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(HERE))
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from harness.cell import execute
    return execute(args.workload, args.seed, args.seconds, bool(args.trace),
                   t_process=T_PROCESS)


if __name__ == "__main__":
    sys.exit(main())
