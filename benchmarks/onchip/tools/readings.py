#!/usr/bin/env python3
"""The two readings each compared number's limit is set from, on the chip,
at the cell's own sizes, in one process for many seeds:

* sound: the system's own payload on the seed's inputs against the plain
  reference in float32, exactly as a run's check computes it;
* control: the plain reference computed in float8 (e4m3, scaled per
  tensor: the precision one step below the configurations' bfloat16) in
  the system's place, against the same float32 reference. For served
  tokens the control reads, at each served position, the gap of the token
  that float8 puts first.

    python3 benchmarks/onchip/tools/readings.py --workload stream.short \
        --seeds 1,2,3 --control-seeds 3

Prints one JSON line per seed and payload, then the largest sound reading
and the smallest control reading of each number.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]


def score_readings(run, spec, control, n=64, k_max=32):
    import numpy as np
    from checks.score import reference_scores
    from harness.seeds import rng
    from payloads.score import Payload
    pl = Payload(run, spec)
    pl.setup()
    r = rng(run.seed, 10)
    pairs = [(int(r.integers(64)), int(r.integers(k_max))) for _ in range(n)]
    tokens = np.concatenate([pl.tokens(c, k) for c, k in pairs])
    got = np.concatenate([pl.fn(0, pl.tokens(c, k))[1] for c, k in pairs])
    pl.free()
    want = reference_scores(pl.model.m, pl.weight_seed, tokens)
    out = {"score_gap": float(np.max(np.abs(got - want)))}
    if control:
        low = reference_scores(pl.model.m, pl.weight_seed, tokens, "fp8")
        out["score_gap.control"] = float(np.max(np.abs(low - want)))
    return out


def train_readings(run, spec, control):
    """The training task of the run's first window iteration. Also, with
    ``control``, two faults planted in the reference put in the system's
    place: a step that leaves the state unchanged (every step's loss is the
    initial parameters' loss on that step's batch and nothing moves: its
    change gap reads 1), and half of each batch left out (the mean over the
    first half's rows)."""
    import jax
    from checks.train import gaps as train_gaps
    from checks.train import leaf_gaps, reference_training
    from payloads.train import Payload, seed_for, train_task
    from reference import mamba2 as RM
    from reference import training as RT
    from repro.launch.mesh import make_mesh
    pl = Payload(run, spec)
    seed = seed_for(run, 0)
    mesh = make_mesh((run.chips, 1), ("data", "model"))
    res = train_task(*pl.args(seed), mesh=mesh)
    got = dict(losses=res["losses"], change=pl.changes(res))
    del res
    m, fam = pl.model.m, pl.model.family
    ref = reference_training(m, fam, seed, pl.steps, pl.batch, pl.seq)

    def read(x, tag):
        out = {}
        for k, v in train_gaps(x, ref).items():
            out[k + tag] = v
        g = leaf_gaps(x["change"], ref["change"], ref)
        out[f"change_worst_leaf{tag}"] = max(g, key=g.get)
        out[f"first_loss_gap{tag}"] = abs(x["losses"][0] - ref["losses"][0]
                                          ) / abs(ref["losses"][0])
        return out

    out = {**read(got, ""), "seed_train": seed, "losses": got["losses"],
           "ref_losses": ref["losses"]}
    if control:
        low = reference_training(m, fam, seed, pl.steps, pl.batch, pl.seq,
                                 "fp8")
        out.update(read(low, ".control"), control_losses=low["losses"])
        half = reference_training(m, fam, seed, pl.steps, pl.batch // 2,
                                  pl.seq)
        out.update(read(half, ".fault_half_batch"))
        params = jax.jit(lambda k: RM.init(k, m))(jax.random.PRNGKey(seed))
        loss = jax.jit(lambda p, b: RM.loss(p, b, m))
        frozen = {"losses": [float(loss(params, RT.batch_at(
            seed, s, pl.batch, pl.seq, m["vocab_size"])))
            for s in range(pl.steps)],
            "change": {k: 0.0 for k in ref["change"]}}
        out.update(read(frozen, ".fault_unchanged"))
    return out


def generate_readings(run, spec, control, tasks=2):
    import numpy as np
    from checks.generate import reference_logits, served_gaps
    from payloads.generate import Payload
    pl = Payload(run, spec)
    pl.setup()
    seqs = np.concatenate([pl.fn(pl.prompt_tokens(it)) for it in range(tasks)])
    pl.free()
    S, V = pl.prompt_len, pl.model.m["vocab_size"]
    ref = reference_logits(pl.model.m, pl.weight_seed, seqs, pl.new)
    out = {"served_gap": float(served_gaps(ref, seqs[:, S:], V).max())}
    if control:
        low = reference_logits(pl.model.m, pl.weight_seed, seqs, pl.new,
                               "fp8")
        first = low[..., :V].argmax(-1)
        out["served_gap.control"] = float(served_gaps(ref, first, V).max())
    return out


READERS = {"score": score_readings, "train": train_readings,
           "generate": generate_readings}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="the control is read on the first N seeds")
    ap.add_argument("--payloads", default="",
                    help="comma-separated subset of the cell's payloads")
    ap.add_argument("--cpu", action="store_true",
                    help="rehearse at the tests' small sizes on the CPU")
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
    bench = S.with_held(S.load_benchmark())
    seeds = [int(s) for s in args.seeds.split(",")]
    best = {}
    for i, seed in enumerate(seeds):
        cell = S.Cell(bench, args.workload)
        devices = D.claim(cell.chips, require_tpu=not args.cpu)
        run = Run(cell, seed, 0, False, devices, time.perf_counter())
        if args.cpu:
            from tests.small import shrink
            shrink(run)
        t = cell.traffic
        specs = t["stages"] if "stages" in t else [t["task"]]
        for spec in specs:
            kind = spec["payload"]
            if kind not in READERS or (args.payloads and kind not in
                                        args.payloads.split(",")):
                continue
            t0 = time.perf_counter()
            r = READERS[kind](run, spec, i < args.control_seeds)
            print(json.dumps({"seed": seed, "payload": kind,
                              "seconds": time.perf_counter() - t0, **r}),
                  flush=True)
            for k, v in r.items():
                if isinstance(v, float):
                    best.setdefault(k, []).append(v)
    for k, vals in sorted(best.items()):
        upper = "." in k          # the control's and the faults' readings
        print(f"READING {k}: {'min' if upper else 'max'} "
              f"{min(vals) if upper else max(vals)!r} over {len(vals)} "
              f"seeds: {vals!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
