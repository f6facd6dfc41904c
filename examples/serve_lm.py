"""Batched serving example: prefill + autoregressive decode with sharded KV
caches over a host mesh; any of the 10 assigned archs via --arch.

  PYTHONPATH=src python examples/serve_lm.py --arch chatglm3-6b
  PYTHONPATH=src python examples/serve_lm.py --arch mamba2-130m --requests 16
"""
import argparse

from repro.configs import ARCH_IDS, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve import serve_batch


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="chatglm3-6b", choices=ARCH_IDS)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=48)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    args = ap.parse_args()
    enable_compile_cache()
    cfg = get_smoke_config(args.arch)
    print(f"[serve_lm] {args.arch} (reduced config, "
          f"{cfg.num_params()/1e3:.0f}K params)")
    stats = serve_batch(cfg, n_requests=args.requests,
                        prompt_len=args.prompt_len,
                        max_new_tokens=args.max_new_tokens)
    print(f"[serve_lm] {stats['tokens_per_s']:.1f} tokens/s")


if __name__ == "__main__":
    main()
