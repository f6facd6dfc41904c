"""End-to-end training driver.

Builds the mesh, shards params/optimizer per policy, runs the data pipeline,
train steps under jit with donation, periodic checkpointing with restart
(``--resume`` restores the latest step — onto a different mesh if the device
count changed: elastic restart), and optional int8 gradient compression.

CPU example (the quickstart path):
  PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m --smoke \
      --steps 20 --batch 8 --seq-len 256
"""
from __future__ import annotations

import argparse
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.checkpoint.checkpoint import CheckpointManager
from repro.configs import get_config, get_smoke_config
from repro.data.pipeline import DataConfig, PrefetchingLoader, make_loader
from repro.distributed import sharding as SH
from repro.distributed.train_step import make_train_step
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import model as M
from repro.optim import adamw


def train(cfg, *, steps: int, global_batch: int, seq_len: int,
          mesh=None, ckpt_dir: str = "", ckpt_every: int = 0,
          resume: bool = False, accum_steps: int = 1,
          compress_grads: bool = False, log_every: int = 10,
          seed: int = 0, opt_cfg=None, quiet: bool = False
          ) -> Dict[str, Any]:
    mesh = mesh if mesh is not None else make_host_mesh()
    opt_cfg = opt_cfg or adamw.OptimizerConfig(total_steps=max(steps, 2),
                                               warmup_steps=max(2, steps // 10))
    dp_axes = SH.batch_axes(mesh, cfg, global_batch)

    # params and optimizer state are made on the mesh's own devices, so a
    # task on one partition leaves nothing on the process's default device
    def init_params():
        return M.init_params(jax.random.PRNGKey(seed), cfg)

    shapes = jax.eval_shape(init_params)
    p_shard = jax.tree.map(lambda s: NamedSharding(mesh, s),
                           SH.params_pspec(cfg, mesh, shapes))
    o_shard = jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        SH.opt_state_pspec(cfg, mesh, jax.eval_shape(adamw.init, shapes)))
    params = jax.jit(init_params, out_shardings=p_shard)()
    opt_state = jax.jit(adamw.init, out_shardings=o_shard)(params)

    start_step = 0
    ckpt = CheckpointManager(ckpt_dir) if ckpt_dir else None
    if ckpt and resume and ckpt.latest_step() is not None:
        restored = ckpt.restore(template={"params": params, "opt": opt_state},
                                shardings={"params": p_shard, "opt": o_shard})
        params = restored["tree"]["params"]
        opt_state = restored["tree"]["opt"]
        start_step = restored["step"]
        if not quiet:
            print(f"[train] resumed from step {start_step} "
                  f"onto {mesh.devices.size} devices")

    dcfg = DataConfig(seq_len=seq_len, global_batch=global_batch, seed=seed)
    stream = make_loader(cfg, dcfg)
    stream.step = start_step
    loader = PrefetchingLoader(iter(stream), depth=2)

    step_fn = make_train_step(
        cfg, opt_cfg, accum_steps=accum_steps,
        grad_compression="int8" if compress_grads else None,
        mesh=mesh, dp_axes=dp_axes)
    b_spec = SH.batch_pspec(cfg, mesh, global_batch)
    jitted = jax.jit(step_fn,
                     in_shardings=(p_shard, o_shard,
                                   None),
                     out_shardings=(p_shard, o_shard, None),
                     donate_argnums=(0, 1))

    losses = []
    t0 = time.time()
    with mesh:
        for step in range(start_step, steps):
            host_batch = next(loader)
            batch = {k: jax.device_put(
                v, NamedSharding(mesh, b_spec.get(k, None) or
                                 jax.sharding.PartitionSpec()))
                for k, v in host_batch.items()}
            params, opt_state, metrics = jitted(params, opt_state, batch)
            loss = float(metrics["loss"])
            losses.append(loss)
            if not quiet and (step % log_every == 0 or step == steps - 1):
                dt = time.time() - t0
                print(f"[train] step {step:5d} loss {loss:8.4f} "
                      f"gnorm {float(metrics['grad_norm']):7.3f} "
                      f"lr {float(metrics['lr']):.2e} ({dt:.1f}s)", flush=True)
            if ckpt and ckpt_every and (step + 1) % ckpt_every == 0:
                ckpt.save(step + 1, {"params": params, "opt": opt_state})
    if ckpt:
        ckpt.save(steps, {"params": params, "opt": opt_state})
        ckpt.wait()
    loader.close()
    return {"losses": losses, "params": params, "opt_state": opt_state,
            "final_loss": losses[-1] if losses else float("nan"),
            "steps": steps}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--d-model", type=int, default=0,
                    help="override d_model for --smoke scaling")
    args = ap.parse_args()
    enable_compile_cache()

    if args.smoke:
        overrides = {}
        if args.d_model:
            overrides = {"d_model": args.d_model}
        cfg = get_smoke_config(args.arch, **overrides)
    else:
        cfg = get_config(args.arch)
    out = train(cfg, steps=args.steps, global_batch=args.batch,
                seq_len=args.seq_len, ckpt_dir=args.ckpt_dir,
                ckpt_every=args.ckpt_every, resume=args.resume,
                accum_steps=args.accum, compress_grads=args.compress_grads)
    print(f"[train] done: final loss {out['final_loss']:.4f}")


if __name__ == "__main__":
    main()
