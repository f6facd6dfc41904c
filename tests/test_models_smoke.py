"""Per-architecture smoke tests (reduced same-family configs): one forward /
train-step / prefill / decode on CPU, asserting shapes and no NaNs — the
assignment's smoke requirement for every arch."""
import jax
import jax.numpy as jnp
import pytest

from conftest import make_batch, make_positions
from repro.configs import ARCH_IDS, get_smoke_config
from repro.distributed.train_step import make_train_step
from repro.models import model as M
from repro.optim import adamw


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_train(arch, rng_key):
    cfg = get_smoke_config(arch)
    B, S = 2, 32
    params = M.init_params(rng_key, cfg)
    batch = make_batch(cfg, rng_key, B, S)
    logits, aux, caches = M.forward(params, cfg, batch, mode="train")
    assert logits.shape == (B, S, cfg.padded_vocab)
    assert caches is None
    assert not bool(jnp.isnan(logits).any()), f"{arch}: NaN logits"
    assert not bool(jnp.isnan(aux)), f"{arch}: NaN aux"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_one_train_step(arch, rng_key):
    cfg = get_smoke_config(arch)
    B, S = 2, 16
    params = M.init_params(rng_key, cfg)
    opt = adamw.init(params)
    step = make_train_step(cfg, adamw.OptimizerConfig(total_steps=10,
                                                      warmup_steps=1))
    batch = make_batch(cfg, rng_key, B, S)
    new_params, new_opt, metrics = jax.jit(step)(params, opt, batch)
    assert jnp.isfinite(metrics["loss"]), f"{arch}: non-finite loss"
    assert float(metrics["loss"]) > 0
    # params actually changed
    diff = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
        a.astype(jnp.float32) - b.astype(jnp.float32)))), params, new_params)
    assert max(jax.tree.leaves(diff)) > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_and_decode_shapes(arch, rng_key):
    cfg = get_smoke_config(arch)
    B, S = 2, 16
    params = M.init_params(rng_key, cfg)
    batch = make_batch(cfg, rng_key, B, S, with_labels=False)
    logits, _, cache = M.forward(params, cfg, batch, mode="prefill")
    assert logits.shape == (B, 1, cfg.padded_vocab)
    assert int(cache["index"]) == S

    dc = M.init_cache(cfg, B, max_len=S + 1)
    dc["index"] = jnp.asarray(S, jnp.int32)
    db = {"tokens": batch["tokens"][:, :1],
          "positions": make_positions(cfg, B, 1, start=S)}
    if cfg.input_mode == "embeddings":
        db["embeds"] = batch["embeds"][:, :1]
    dl, nc = M.decode(params, cfg, db, dc)
    assert dl.shape == (B, 1, cfg.padded_vocab)
    assert not bool(jnp.isnan(dl).any())
    assert int(nc["index"]) == S + 1


@pytest.mark.parametrize("arch", ["chatglm3-6b", "gemma-7b", "qwen2-vl-7b",
                                  "mamba2-130m", "zamba2-7b",
                                  "musicgen-medium", "stablelm-12b",
                                  "stablelm-3b"])
def test_decode_matches_full_forward(arch, rng_key):
    """Sequential decode from empty cache == teacher-forced forward (exact
    cache/RoPE-offset/SSD-step consistency). MoE archs are checked separately
    with no-drop capacity."""
    cfg = get_smoke_config(arch, dtype="float32")
    B, S = 2, 12
    params = M.init_params(rng_key, cfg)
    batch = make_batch(cfg, rng_key, B, S, with_labels=False)
    full, _, _ = M.forward(params, cfg, batch, mode="train")
    cache = M.init_cache(cfg, B, max_len=S)
    outs = []
    for t in range(S):
        db = {"tokens": batch["tokens"][:, t:t + 1],
              "positions": make_positions(cfg, B, 1, start=t)}
        if cfg.input_mode == "embeddings":
            db["embeds"] = batch["embeds"][:, t:t + 1]
        lg, cache = M.decode(params, cfg, db, cache)
        outs.append(lg)
    dec = jnp.concatenate(outs, axis=1)
    rel = float(jnp.max(jnp.abs(dec - full))) / (
        float(jnp.max(jnp.abs(full))) + 1e-9)
    assert rel < 2e-3, f"{arch}: decode/forward mismatch rel={rel:.2e}"


@pytest.mark.parametrize("arch", ["deepseek-v2-lite-16b",
                                  "phi3.5-moe-42b-a6.6b"])
def test_moe_decode_matches_with_nodrop_capacity(arch, rng_key):
    cfg = get_smoke_config(arch, dtype="float32", capacity_factor=8.0)
    B, S = 2, 10
    params = M.init_params(rng_key, cfg)
    batch = make_batch(cfg, rng_key, B, S, with_labels=False)
    full, _, _ = M.forward(params, cfg, batch, mode="train")
    cache = M.init_cache(cfg, B, max_len=S)
    outs = []
    for t in range(S):
        db = {"tokens": batch["tokens"][:, t:t + 1],
              "positions": make_positions(cfg, B, 1, start=t)}
        lg, cache = M.decode(params, cfg, db, cache)
        outs.append(lg)
    dec = jnp.concatenate(outs, axis=1)
    rel = float(jnp.max(jnp.abs(dec - full))) / float(jnp.max(jnp.abs(full)))
    assert rel < 2e-3, f"{arch}: rel={rel:.2e}"


def test_scan_vs_unrolled_equivalence(rng_key):
    for arch in ("chatglm3-6b", "zamba2-7b", "deepseek-v2-lite-16b"):
        cfg_s = get_smoke_config(arch, dtype="float32")
        cfg_u = get_smoke_config(arch, dtype="float32", scan_layers=False)
        params = M.init_params(rng_key, cfg_s)
        batch = make_batch(cfg_s, rng_key, 2, 8, with_labels=False)
        a, _, _ = M.forward(params, cfg_s, batch, mode="train")
        b, _, _ = M.forward(params, cfg_u, batch, mode="train")
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4


def test_gemma_embedding_scaling(rng_key):
    cfg = get_smoke_config("gemma-7b", dtype="float32")
    from repro.models import layers as L
    p = L.init_embedding(rng_key, cfg.padded_vocab, cfg.d_model, jnp.float32)
    toks = jnp.zeros((1, 4), jnp.int32)
    x = L.embed(p, toks, cfg)
    base = jnp.take(p["table"], toks, axis=0)
    assert jnp.allclose(x, base * jnp.sqrt(float(cfg.d_model)))


def test_moe_aux_loss_nonzero(rng_key):
    cfg = get_smoke_config("phi3.5-moe-42b-a6.6b")
    params = M.init_params(rng_key, cfg)
    batch = make_batch(cfg, rng_key, 2, 16)
    _, aux, _ = M.forward(params, cfg, batch, mode="train")
    assert float(aux) > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_pad_cache_gives_decode_layout(arch, rng_key):
    """Prefill's caches, grown by ``pad_cache``, have ``init_cache``'s
    shapes: GQA k/v turned from (..., B, S, KV, hd) to (..., B, KV, hd, S)
    with the prompt's positions first and zeros after, MLA latents padded
    in place. One decode step on them through the Pallas decode kernel
    (interpret mode) gives the XLA path's logits."""
    import dataclasses

    import numpy as np
    from jax.experimental.pallas import tpu as pltpu

    from repro.distributed.serve_step import pad_cache
    cfg = get_smoke_config(arch, dtype="float32")
    B, S, n = 2, 16, 1152              # as generate.zamba2: 3 blocks of 384
    params = M.init_params(rng_key, cfg)
    batch = make_batch(cfg, rng_key, B, S, with_labels=False)
    _, _, cache = M.forward(params, cfg, batch, mode="prefill")
    grown = pad_cache(cache, cfg, n)
    want = jax.eval_shape(lambda: M.init_cache(cfg, B, n))
    assert (jax.tree.map(lambda x: (x.shape, x.dtype), grown)
            == jax.tree.map(lambda x: (x.shape, x.dtype), want))
    for (path, old), new in zip(jax.tree_util.tree_flatten_with_path(cache)[0],
                                jax.tree.leaves(grown)):
        name = path[-1].key
        if name in ("k", "v"):
            old = jnp.moveaxis(old, -3, -1)
            assert np.array_equal(new[..., :S], old)
            assert not np.asarray(new[..., S:]).any()
        elif name in ("c_kv", "k_rope"):
            assert np.array_equal(new[..., :S, :], old)
            assert not np.asarray(new[..., S:, :]).any()
    db = {"tokens": batch["tokens"][:, :1],
          "positions": make_positions(cfg, B, 1, start=S)}
    if cfg.input_mode == "embeddings":
        db["embeds"] = batch["embeds"][:, :1]
    xla, _ = M.decode(params, cfg, db, grown)
    with pltpu.force_tpu_interpret_mode():
        pal, _ = M.decode(params, dataclasses.replace(cfg, use_pallas=True),
                          db, grown)
    rel = float(jnp.max(jnp.abs(pal - xla))) / float(jnp.max(jnp.abs(xla)))
    assert rel < 1e-4, f"{arch}: pallas/xla decode rel={rel:.2e}"
