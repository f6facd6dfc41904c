"""Serving driver: batched prefill + autoregressive decode with sharded
caches; used by examples/serve_lm.py and the IMPECCABLE surrogate-inference
stage in real mode."""
from __future__ import annotations

import argparse
import functools
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.configs.base import ModelConfig
from repro.distributed.serve_step import (KV_SEQ_FROM_END, make_decode_step,
                                          make_prefill_step, pad_cache,
                                          sample)
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M


def positions(cfg: ModelConfig, B: int, S: int, start: int = 0):
    base = start + jnp.arange(S, dtype=jnp.int32)
    if cfg.rope_kind == "mrope":
        return jnp.broadcast_to(base[None, None], (3, B, S))
    return jnp.broadcast_to(base[None], (B, S))


@functools.lru_cache(maxsize=None)
def serve_steps(cfg: ModelConfig):
    """Jitted (prefill, decode) for ``cfg``, built once per config so
    repeated ``generate()`` calls reuse their compiled programs."""
    return (jax.jit(make_prefill_step(cfg)),
            jax.jit(make_decode_step(cfg), donate_argnums=(2,)))


def cache_bytes(cache) -> Dict[str, int]:
    """Bytes a decode cache holds, by kind: attention KV (``kv_bytes``) and
    SSM state with its convolution windows (``ssm_bytes``)."""
    out = {"kv_bytes": 0, "ssm_bytes": 0}
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        name = getattr(path[-1], "key", None)
        if name in KV_SEQ_FROM_END:
            out["kv_bytes"] += leaf.nbytes
        elif name == "state" or str(name).startswith("conv_"):
            out["ssm_bytes"] += leaf.nbytes
    return out


def cache_len(n: int) -> int:
    """Decode's cache length for ``n`` positions: whole 128-lane tiles, as
    the chip lays out a cache whose sequence axis is last, so the decode
    attention kernel reads it in 128-aligned blocks with no pad."""
    return -(-n // 128) * 128


def generate(params, cfg: ModelConfig, prompts: jnp.ndarray, *,
             max_new_tokens: int = 32, temperature: float = 0.0,
             key=None, mesh=None, stamps: Optional[Dict] = None
             ) -> jnp.ndarray:
    """prompts (B, S) int32 -> (B, S + max_new_tokens).

    Prefill and decode run under the profiler annotations ``serve:prefill``
    and ``serve:decode``. Given ``stamps``, fills it with host-clock
    (``time.perf_counter``) stamps ``prefill_dispatch``, ``first_token``
    (the prefill's token is ready) and ``last_token``, and the counters
    ``decode_steps`` and ``kv_bytes`` / ``ssm_bytes`` (the decode cache's
    bytes by kind); the first token is then waited for before decoding."""
    B, S = prompts.shape
    key = key if key is not None else jax.random.PRNGKey(0)
    prefill, decode = serve_steps(cfg)

    with jax.profiler.TraceAnnotation("serve:prefill"):
        if stamps is not None:
            stamps["prefill_dispatch"] = time.perf_counter()
        batch = {"tokens": prompts, "positions": positions(cfg, B, S)}
        logits, cache = prefill(params, batch)
        cache = pad_cache(cache, cfg, cache_len(S + max_new_tokens))
        tokens = [sample(logits, key, temperature, cfg.vocab_size)]
        if stamps is not None:
            tokens[0].block_until_ready()
            stamps["first_token"] = time.perf_counter()
            stamps.update(cache_bytes(cache))
    out = [prompts]
    with jax.profiler.TraceAnnotation("serve:decode"):
        for t in range(max_new_tokens - 1):
            key, sub = jax.random.split(key)
            db = {"tokens": tokens[-1],
                  "positions": positions(cfg, B, 1, start=S + t)}
            logits, cache = decode(params, db, cache)
            tokens.append(sample(logits, sub, temperature, cfg.vocab_size))
        if stamps is not None:
            tokens[-1].block_until_ready()
            stamps["last_token"] = time.perf_counter()
            stamps["decode_steps"] = max_new_tokens - 1
    return jnp.concatenate(out + tokens, axis=1)


def serve_batch(cfg: ModelConfig, *, n_requests: int = 8, prompt_len: int = 64,
                max_new_tokens: int = 16, seed: int = 0, params=None,
                quiet: bool = False) -> Dict[str, float]:
    """Batched-request serving measurement (throughput in tokens/s)."""
    key = jax.random.PRNGKey(seed)
    params = params if params is not None else M.init_params(key, cfg)
    prompts = jax.random.randint(key, (n_requests, prompt_len), 0,
                                 cfg.vocab_size, dtype=jnp.int32)
    t0 = time.time()
    out = generate(params, cfg, prompts, max_new_tokens=max_new_tokens)
    out.block_until_ready()
    dt = time.time() - t0
    toks = n_requests * max_new_tokens
    if not quiet:
        print(f"[serve] {n_requests} requests x {max_new_tokens} new tokens "
              f"in {dt:.2f}s -> {toks/dt:.1f} tok/s")
    assert out.shape == (n_requests, prompt_len + max_new_tokens)
    assert not bool(jnp.isnan(out).any())
    return {"tokens_per_s": toks / dt, "wall_s": dt}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    args = ap.parse_args()
    enable_compile_cache()
    cfg = (get_smoke_config(args.arch) if args.smoke
           else get_config(args.arch))
    serve_batch(cfg, n_requests=args.requests, prompt_len=args.prompt_len,
                max_new_tokens=args.max_new_tokens)


if __name__ == "__main__":
    main()
