"""``harness/flops_zamba2.py`` against XLA's cost analysis of the system's
own zamba2 forward and decode step, at a reduced size with the published
layout (two shared blocks, irregular ids, two B/C groups) on the CPU.

As in ``test_flops.py``: XLA counts the unmasked programs and elementwise
work, so the count, with ``causal_half=False``, lies below XLA's by at most
MARGIN and never above it. The system's XLA path also computes C.B^T once
per head where the model needs it once per B/C group; that difference is
added back before comparing the prefill.
"""
import dataclasses

import jax
import jax.numpy as jnp

from harness import flops_zamba2 as FZ

MARGIN = 0.03
B, S, START = 2, 256, 40


def _cfg():
    from repro.configs import get_config
    return dataclasses.replace(
        get_config("zamba2-7b"), num_layers=6, hybrid_layer_ids=(1, 3, 4),
        d_model=256, num_heads=4, num_kv_heads=4, head_dim=128, d_ff=512,
        adapter_rank=16, vocab_size=4000, ssm_state=64, ssm_chunk=64,
        scan_layers=False, dtype="float32")


def _xla(fn, *args):
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    return float(cost["flops"])


def _setup():
    from repro.models import model as M
    cfg = _cfg()
    m = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    params = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0),
                                                  cfg))
    return M, cfg, m, params


def test_prefill_count_matches_xla():
    M, cfg, m, params = _setup()
    toks = jax.ShapeDtypeStruct((B, S), jnp.int32)
    xla = _xla(lambda p, t, q: M.forward(p, cfg, {"tokens": t,
                                                  "positions": q})[0],
               params, toks, toks)
    L = cfg.ssm_chunk
    per_head_cb = (B * cfg.num_layers * (S // L) * 2
                   * (cfg.ssm_heads - cfg.ssm_groups) * L * L * cfg.ssm_state)
    ours = FZ.prefill(m, B, S, causal_half=False) + per_head_cb
    assert xla * (1 - MARGIN) <= ours <= xla, (ours, xla)


def test_decode_step_count_matches_xla():
    M, cfg, m, params = _setup()
    cache = jax.eval_shape(lambda: M.init_cache(cfg, B, START + 1))
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    xla = _xla(lambda p, t, q, c: M.decode(p, cfg, {"tokens": t,
                                                    "positions": q}, c)[0],
               params, tok, tok, cache)
    ours = FZ.decode_step(m, B, START)
    assert xla * (1 - MARGIN) <= ours <= xla, (ours, xla)


def test_generate_is_prefill_and_decode_steps():
    _, _, m, _ = _setup()
    assert FZ.generate(m, 4, 64, 3) == (
        FZ.prefill(m, 4, 64, logits_per_row=1) + FZ.decode_step(m, 4, 64)
        + FZ.decode_step(m, 4, 65))
