"""zamba2-7b's published layout against the plain reference
(``zamba2_reference.py``), at a small size on the CPU with seeded weights:
two shared blocks used in turn at irregular ids (1, 3, 4), B/C in 2 groups,
per-invocation adapters and linears. Each planted departure from the
published block must fail the same comparison that the system passes.

Tolerance: the system computes in float32 (chunked SSD, masked attention
with its own accumulation order), the reference in float64 one token at a
time; their logits agree to about 1e-6 of the largest logit, so a relative
gap of 1e-4 is sound. Every departure moves the logits by over a tenth.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import zamba2_reference as R
from conftest import make_positions
from repro.configs import get_smoke_config
from repro.launch import serve
from repro.models import layers as L
from repro.models import model as M

TOL = 1e-4
B, S = 2, 12


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("zamba2-7b", dtype="float32")
    params = M.init_params(jax.random.PRNGKey(3), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (B, S), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    m = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    return cfg, params, tokens, m


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def test_small_layout_is_the_published_one(setup):
    cfg, _, _, _ = setup
    assert cfg.num_mem_blocks == 2 and cfg.ssm_groups == 2
    assert cfg.head_dim * cfg.num_heads == 2 * cfg.d_model
    gaps = np.diff(cfg.hybrid_layer_ids)
    assert len(set(gaps)) > 1                   # irregular spacing
    assert [j for _, _, j in M.hybrid_runs(cfg)] == [None, 0, 1, 2]


def test_forward_matches_reference(setup):
    cfg, params, tokens, m = setup
    got, _, _ = M.forward(params, cfg, {
        "tokens": tokens, "positions": make_positions(cfg, B, S)})
    assert _rel(got, R.logits(params, tokens, m)) < TOL


def test_prefill_then_decode_matches_reference(setup):
    cfg, params, tokens, m = setup
    want = R.logits(params, tokens, m)
    S0 = 5
    lg, _, cache = M.forward(params, cfg, {
        "tokens": tokens[:, :S0], "positions": make_positions(cfg, B, S0)},
        mode="prefill")
    from repro.distributed.serve_step import pad_cache
    cache = pad_cache(cache, cfg, S)
    got = [lg]
    for t in range(S0, S):
        lg, cache = M.decode(params, cfg, {
            "tokens": tokens[:, t:t + 1],
            "positions": make_positions(cfg, B, 1, start=t)}, cache)
        got.append(lg)
    got = jnp.concatenate(got, axis=1)          # positions S0-1 .. S-1
    assert _rel(got, want[:, S0 - 1:]) < TOL


@pytest.mark.parametrize("departure", [
    {"one_block": True}, {"x0": False}, {"adapters": False},
    {"grouped_norm": False}])
def test_departures_fail(setup, departure):
    cfg, params, tokens, m = setup
    got, _, _ = M.forward(params, cfg, {
        "tokens": tokens, "positions": make_positions(cfg, B, S)})
    assert _rel(got, R.logits(params, tokens, m, **departure)) > 100 * TOL


def test_grouped_norm_at_one_group_is_the_old_norm():
    x = jax.random.normal(jax.random.PRNGKey(5), (3, 7, 64), jnp.bfloat16)
    p = {"scale": 0.1 * jax.random.normal(jax.random.PRNGKey(6), (64,))}
    one = L.group_rmsnorm(p, x, 1e-5, 1)
    assert one.dtype == x.dtype
    assert np.array_equal(np.asarray(one), np.asarray(L.rmsnorm(p, x, 1e-5)))
    # at two groups each half is normalized on its own
    two = L.group_rmsnorm(p, x, 1e-5, 2)
    halves = [L.rmsnorm({"scale": p["scale"][i:i + 32]}, x[..., i:i + 32],
                        1e-5) for i in (0, 32)]
    assert np.array_equal(np.asarray(two),
                          np.asarray(jnp.concatenate(halves, -1)))


def test_num_params_counts_the_init(setup):
    cfg, params, _, _ = setup
    assert cfg.padded_vocab == cfg.vocab_size
    assert sum(x.size for x in jax.tree.leaves(params)) == cfg.num_params()


def test_generate_stamps_and_counters(setup, monkeypatch):
    cfg, params, tokens, _ = setup
    seen = []

    class Annotation:
        def __init__(self, name):
            seen.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(serve.jax.profiler, "TraceAnnotation", Annotation)
    new, stamps = 4, {}
    out = serve.generate(params, cfg, tokens, max_new_tokens=new,
                         stamps=stamps)
    assert out.shape == (B, S + new)
    assert seen == ["serve:prefill", "serve:decode"]
    assert (stamps["prefill_dispatch"] <= stamps["first_token"]
            <= stamps["last_token"])
    assert stamps["decode_steps"] == new - 1
    cache = M.init_cache(cfg, B, serve.cache_len(S + new))
    flat = jax.tree_util.tree_flatten_with_path(cache)[0]
    kv = sum(x.nbytes for p, x in flat if p[-1].key in ("k", "v"))
    ssm = sum(x.nbytes for p, x in flat if p[-1].key == "state"
              or p[-1].key.startswith("conv_"))
    assert kv > 0 and ssm > 0
    assert (stamps["kv_bytes"], stamps["ssm_bytes"]) == (kv, ssm)
