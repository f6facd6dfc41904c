#!/usr/bin/env python3
"""The readings ``generate.zamba2``'s limit is set from, on the chip, at the
cell's own sizes, in one process for many seeds:

* sound: the payload's own tasks (two, as the check samples) against the
  plain reference in float32, exactly as a run's check computes
  ``served_gap``;
* control: the plain reference computed in float8 (``reference/numerics``)
  in the system's place: the gap of the token float8 puts first;
* faults, planted in the system as ``tests/test_zamba2_cell.py`` plants
  them: one shared block serving every invocation (``one_block``), x0 left
  out of the blocks' input (``no_x0``), both at once (``simplified``, the
  model as the system had it before it followed the published layout), and
  the invocations' adapters left out (``no_adapters``).

    python3 benchmarks/onchip/tools/zamba2_readings.py --seeds 1,2,3 \
        --control-seeds 2 --fault-seeds 1

Prints one JSON line per seed, then the largest sound reading and the
smallest reading of the control and of each fault. ``--cpu`` rehearses at
the benchmark tests' small size.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
WORKLOAD = "generate.zamba2"


def _faults():
    """name -> (change of the parameters or None, patch of the system's
    code or None), as the benchmark's tests plant them."""
    from tests import test_zamba2_cell as T
    return {"one_block": (T._one_block, None),
            "no_adapters": (T._no_adapters, None),
            "no_x0": (None, T._no_x0),
            "simplified": (T._one_block, T._no_x0)}


def readings(run, spec, control: bool, faults: bool, tasks: int = 2):
    import numpy as np
    import pytest
    from checks.generate import served_gaps
    from checks.generate_loop import reference_logits
    from payloads.generate_loop import Payload
    from repro.launch import serve
    pl = Payload(run, spec)
    pl.setup()
    prompts = [pl.tokens(c, 0) for c in range(tasks)]

    def served():
        return np.concatenate([pl.fn(0, p)[1] for p in prompts])

    runs = {"": served()}
    if faults:
        sound = pl.params
        for name, (change, patch) in _faults().items():
            serve.serve_steps.cache_clear()
            pl.params = change(sound) if change else sound
            with pytest.MonkeyPatch.context() as mp:
                if patch:
                    patch(mp)
                runs["." + name] = served()
            pl.params = sound
        serve.serve_steps.cache_clear()
    pl.free()
    S, V = pl.prompt_len, pl.model.m["vocab_size"]
    out = {}
    for tag, seqs in runs.items():
        ref = reference_logits(pl.model.m, pl.weight_seed, seqs, pl.new)
        out["served_gap" + tag] = float(served_gaps(ref, seqs[:, S:],
                                                    V).max())
        if control and not tag:
            low = reference_logits(pl.model.m, pl.weight_seed, seqs, pl.new,
                                   "fp8")
            first = low[..., :V].argmax(-1)
            out["served_gap.control"] = float(served_gaps(ref, first,
                                                          V).max())
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="the control is read on the first N seeds")
    ap.add_argument("--fault-seeds", type=int, default=2,
                    help="the faults are read on the first N seeds")
    ap.add_argument("--cpu", action="store_true",
                    help="rehearse at the tests' small size on the CPU")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(HERE))
    import jax
    if not args.cpu:          # share the benchmark's compile cache
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from harness import device as D
    from harness import spec as S
    from harness.cell import Run
    bench = S.load_benchmark()
    best = {}
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        cell = S.Cell(bench, WORKLOAD)
        devices = D.claim(cell.chips, require_tpu=not args.cpu)
        run = Run(cell, seed, 0, False, devices, time.perf_counter())
        if args.cpu:
            from tests.test_zamba2_cell import small_zamba2
            small_zamba2(run)
        t0 = time.perf_counter()
        r = readings(run, cell.traffic["task"], i < args.control_seeds,
                     i < args.fault_seeds)
        print(json.dumps({"seed": seed, "seconds": time.perf_counter() - t0,
                          **r}), flush=True)
        for k, v in r.items():
            best.setdefault(k, []).append(v)
    for k, vals in sorted(best.items()):
        upper = "." in k          # the control's and the faults' readings
        print(f"READING {k}: {'min' if upper else 'max'} "
              f"{min(vals) if upper else max(vals)!r} over {len(vals)} "
              f"seeds: {vals!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
