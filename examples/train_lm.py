"""End-to-end LM pretraining driver: a ~100M-parameter mamba2-family model
trained for a few hundred steps with checkpoint/restart.

Full run (a few hours on this CPU):
  PYTHONPATH=src python examples/train_lm.py --steps 300
Quick check:
  PYTHONPATH=src python examples/train_lm.py --steps 30 --d-model 256
"""
import argparse
import dataclasses

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=512)
    ap.add_argument("--d-model", type=int, default=768,
                    help="768 = the true mamba2-130m width (~130M params)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config("mamba2-130m")
    if args.d_model != cfg.d_model:
        heads_dim = 64
        cfg = dataclasses.replace(
            cfg, d_model=args.d_model,
            num_layers=max(2, cfg.num_layers * args.d_model // 768 // 2))
    print(f"[train_lm] {cfg.name}: {cfg.num_params()/1e6:.1f}M params, "
          f"{cfg.num_layers} layers, d_model={cfg.d_model}")
    out = train(cfg, steps=args.steps, global_batch=args.batch,
                seq_len=args.seq_len, ckpt_dir=args.ckpt_dir,
                ckpt_every=50, resume=args.resume, log_every=10)
    first, last = out["losses"][0], out["final_loss"]
    print(f"[train_lm] loss {first:.3f} -> {last:.3f} over {args.steps} steps")
    assert last < first, "training failed to reduce loss"


if __name__ == "__main__":
    main()
