"""FLOP counts against XLA's cost analysis of the system's own forward, at
reduced sizes on the CPU, and the peak table.

XLA counts every operation of the unmasked programs (the full square of
the SSD blocks and of attention scores), so the counts are compared with
``causal_half=False``. XLA also counts elementwise work (norms,
activations, softmax, the decay exponentials) that the benchmark leaves
out, so the benchmark's count may lie below XLA's by at most MARGIN and
never above it.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from harness import device as D
from harness import flops

MARGIN = 0.03


def _xla_flops(cfg, batch, seq):
    from repro.models import model as M
    params = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg))
    toks = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    pos = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    fn = jax.jit(lambda p, t, q: M.forward(p, cfg, {"tokens": t,
                                                    "positions": q})[0])
    cost = fn.lower(params, toks, pos).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    return float(cost["flops"])


def _sizes(cfg, keys):
    return {k: getattr(cfg, k) for k in keys}


def test_mamba2_forward_count_matches_xla():
    from repro.configs import get_config
    cfg = dataclasses.replace(
        get_config("mamba2-130m"), num_layers=2, d_model=256,
        vocab_size=4000, ssm_state=64, ssm_chunk=64, scan_layers=False,
        dtype="float32")
    m = _sizes(cfg, ["num_layers", "d_model", "vocab_size",
                     "vocab_pad_multiple", "ssm_state", "ssm_head_dim",
                     "ssm_expand", "ssm_conv", "ssm_chunk", "ssm_groups"])
    ours = flops.mamba2_forward(m, 2, 256, causal_half=False)
    xla = _xla_flops(cfg, 2, 256)
    assert xla * (1 - MARGIN) <= ours <= xla, (ours, xla)


def test_dense_forward_count_matches_xla():
    from repro.configs import get_config
    cfg = dataclasses.replace(
        get_config("stablelm-3b"), num_layers=2, d_model=256, num_heads=4,
        num_kv_heads=4, head_dim=64, d_ff=512, vocab_size=4000,
        scan_layers=False, dtype="float32")
    m = _sizes(cfg, ["num_layers", "d_model", "vocab_size",
                     "vocab_pad_multiple", "num_heads", "num_kv_heads",
                     "head_dim", "d_ff"])
    ours = flops.dense_forward(m, 2, 128, causal_half=False)
    xla = _xla_flops(cfg, 2, 128)
    assert xla * (1 - MARGIN) <= ours <= xla, (ours, xla)


def test_training_is_three_forwards():
    m = {"num_layers": 2, "d_model": 64, "vocab_size": 300,
         "vocab_pad_multiple": 32, "ssm_state": 16, "ssm_head_dim": 16,
         "ssm_expand": 2, "ssm_conv": 4, "ssm_chunk": 32, "ssm_groups": 1}
    assert flops.train(m, 2, 64, 5, "ssm") == 15 * flops.mamba2_forward(
        m, 2, 64)


def test_peak_table():
    assert D.peak("TPU v5 lite")["bf16_flops"] == 197e12
    assert D.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        D.peak("TPU v9 imaginary")
