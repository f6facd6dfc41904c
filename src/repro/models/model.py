"""The unified decoder-LM trunk covering all 10 assigned architectures.

One functional model, four families of layer stack:
  * dense / audio / vlm : [norm→attn, norm→mlp] × L, scanned
  * moe                 : optional leading dense layers + [norm→attn, norm→moe] × L
  * ssm                 : [norm→mamba2] × L, scanned
  * hybrid (zamba2)     : [norm→mamba2] × L, scanned in runs between the layers
                          of ``hybrid_layer_ids``; the j-th such layer's mixer
                          input also gets shared block j mod ``num_mem_blocks``
                          (attention+MLP over concat(h, x0)) with invocation
                          j's own LoRA and linear (equations: configs/zamba2_7b.py)

Layers are stacked (leading L dim) and executed with ``lax.scan`` so the HLO
stays small at 512-device SPMD compiles; ``cfg.remat`` selects the activation
checkpoint policy applied to the scanned body.

Modes: ``forward(..., mode='train')`` full logits; ``mode='prefill'`` last-token
logits + filled caches; ``decode(...)`` single-token step against caches.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib

Params = Dict[str, Any]


# ===================================================================== helpers
def _remat(cfg: ModelConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.dots_saveable)
    return jax.checkpoint(fn)


def _scan(cfg: ModelConfig, body, carry, xs):
    """lax.scan over stacked layers, or an unrolled python loop when
    ``cfg.scan_layers=False`` (the dry-run uses unrolled HLO so that
    cost_analysis sees true trip counts; see launch/dryrun.py)."""
    if cfg.scan_layers:
        return jax.lax.scan(body, carry, xs)
    n = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        xi = jax.tree.map(lambda t: t[i], xs)
        carry, y = body(carry, xi)
        ys.append(y)
    if ys and jax.tree.leaves(ys[0]):
        ys = jax.tree.map(lambda *ts: jnp.stack(ts), *ys)
    else:
        ys = ys[0] if ys else None
    return carry, ys


def _split_stack(key, n: int):
    return jax.random.split(key, n)


# ================================================================ block: dense
def init_dense_block(key, cfg: ModelConfig, dtype, *, use_moe: bool, d_ff: int):
    k1, k2 = jax.random.split(key)
    p = {"norm1": L.init_rmsnorm(cfg.d_model, dtype),
         "norm2": L.init_rmsnorm(cfg.d_model, dtype)}
    p["attn"] = (attn.init_mla(k1, cfg, dtype) if cfg.use_mla
                 else attn.init_gqa(k1, cfg, dtype))
    if use_moe:
        p["moe"] = moe_lib.init_moe(k2, cfg, dtype)
    else:
        import dataclasses
        mcfg = cfg if d_ff == cfg.d_ff else dataclasses.replace(cfg, d_ff=d_ff)
        p["mlp"] = L.init_mlp(k2, mcfg, d_ff, dtype)
    return p


def dense_block_full(p, x, cfg: ModelConfig, positions, *, use_moe: bool,
                     return_kv: bool):
    h = L.rmsnorm(p["norm1"], x, cfg.norm_eps)
    if cfg.use_mla:
        h, kv = attn.mla_full(p["attn"], h, cfg, positions, return_kv=return_kv)
    else:
        h, kv = attn.gqa_full(p["attn"], h, cfg, positions, return_kv=return_kv)
    x = x + h
    h = L.rmsnorm(p["norm2"], x, cfg.norm_eps)
    if use_moe:
        h, aux = moe_lib.moe_apply(p["moe"], h, cfg)
    else:
        h, aux = L.mlp(p["mlp"], h, cfg), jnp.zeros((), jnp.float32)
    return x + h, kv, aux


def dense_block_decode(p, x, cfg: ModelConfig, positions, cache, index, *,
                       use_moe: bool):
    h = L.rmsnorm(p["norm1"], x, cfg.norm_eps)
    if cfg.use_mla:
        h, c1, c2 = attn.mla_decode(p["attn"], h, cfg, positions,
                                    cache["c_kv"], cache["k_rope"], index)
        new_cache = {"c_kv": c1, "k_rope": c2}
    else:
        h, ck, cv = attn.gqa_decode(p["attn"], h, cfg, positions,
                                    cache["k"], cache["v"], index)
        new_cache = {"k": ck, "v": cv}
    x = x + h
    h = L.rmsnorm(p["norm2"], x, cfg.norm_eps)
    if use_moe:
        h, _ = moe_lib.moe_apply(p["moe"], h, cfg)
    else:
        h = L.mlp(p["mlp"], h, cfg)
    return x + h, new_cache


# ================================================================== block: ssm
def init_ssm_block(key, cfg: ModelConfig, dtype):
    return {"norm1": L.init_rmsnorm(cfg.d_model, dtype),
            "ssm": ssm_lib.init_mamba2(key, cfg, dtype)}


def ssm_block_full(p, x, cfg: ModelConfig, *, return_cache: bool,
                   inject=None):
    """x + mamba2(norm(x + inject)): ``inject`` (a shared block's output,
    zamba2) joins the mixer's input and not the residual."""
    h = L.rmsnorm(p["norm1"], x if inject is None else x + inject,
                  cfg.norm_eps)
    h, cache = ssm_lib.mamba2_full(p["ssm"], h, cfg, return_cache=return_cache)
    return x + h, cache


def ssm_block_decode(p, x, cfg: ModelConfig, cache, inject=None):
    h = L.rmsnorm(p["norm1"], x if inject is None else x + inject,
                  cfg.norm_eps)
    h, new_cache = ssm_lib.mamba2_decode(p["ssm"], h, cfg, cache)
    return x + h, new_cache


# ========================================================= hybrid (zamba2)
def hybrid_runs(cfg: ModelConfig) -> List[Tuple[int, int, Optional[int]]]:
    """The mamba2 layers in runs ``(start, end, j)``: run j starts at the
    j-th of ``hybrid_layer_ids``, whose mixer input gets the j-th shared
    block invocation; a run before the first id has j None."""
    ids = cfg.hybrid_layer_ids
    bounds = sorted({0, *ids, cfg.num_layers})
    return [(a, b, ids.index(a) if a in ids else None)
            for a, b in zip(bounds[:-1], bounds[1:])]


def init_shared_block(key, cfg: ModelConfig, dtype):
    """One shared block: attention from concat(h, x0) (2 d wide) to d, and
    a gated MLP; no residual of its own."""
    k1, k2 = jax.random.split(key)
    d = cfg.d_model
    return {"norm_in": L.init_rmsnorm(2 * d, dtype),
            "attn": attn.init_gqa(k1, cfg, dtype, d_in=2 * d),
            "norm_ff": L.init_rmsnorm(d, dtype),
            "mlp": L.init_mlp(k2, cfg, cfg.d_ff, dtype)}


def init_invocation(key, cfg: ModelConfig, dtype):
    """What one invocation of a shared block owns: the LoRA (A: d -> rank,
    B: rank -> 2 d_ff) on its MLP's gate/up projection, and the d x d
    linear after the block."""
    ka, kb, kl = jax.random.split(key, 3)
    d, r = cfg.d_model, cfg.adapter_rank
    return {"lora_a": L.init_linear(ka, d, r, dtype),
            "lora_b": L.init_linear(kb, r, 2 * cfg.d_ff, dtype),
            "linear": L.init_linear(kl, d, d, dtype)}


def shared_block(p, inv, h, x0, cfg: ModelConfig, positions, *, kv=None,
                 index=None, return_kv: bool = False):
    """Invocation ``inv`` of shared block ``p``: its output t, and its KV
    cache. Full sequence when ``kv`` is None (the KV returned with
    ``return_kv``), else one token against ``kv`` at ``index``. Scores are
    scaled by (head_dim / 2)^-1/2, as zamba2 scales them."""
    a = L.rmsnorm(p["norm_in"], jnp.concatenate([h, x0], axis=-1),
                  cfg.norm_eps)
    scale = (cfg.head_dim / 2) ** -0.5
    if kv is None:
        o, new = attn.gqa_full(p["attn"], a, cfg, positions,
                               return_kv=return_kv, scale=scale)
        new = None if new is None else {"k": new[0], "v": new[1]}
    else:
        o, ck, cv = attn.gqa_decode(p["attn"], a, cfg, positions, kv["k"],
                                    kv["v"], index, scale=scale)
        new = {"k": ck, "v": cv}
    m = L.rmsnorm(p["norm_ff"], o, cfg.norm_eps)
    ff = cfg.d_ff
    lora = L.linear(inv["lora_b"], L.linear(inv["lora_a"], m))
    g = L.linear(p["mlp"]["w_gate"], m) + lora[..., :ff]
    u = L.linear(p["mlp"]["w_in"], m) + lora[..., ff:]
    t = L.linear(p["mlp"]["w_out"], L.act(cfg.act, g) * u)
    return L.linear(inv["linear"], t), new


def _hybrid_stack(cfg: ModelConfig, params, x, invoke, layer, caches, *,
                  remat: bool):
    """The hybrid trunk from x0 = x: each run of mamba2 layers, scanned over
    the run's own stack of layers (and of SSM caches, where ``caches``), its
    first layer's mixer fed the output of its shared block invocation.
    ``invoke(shared, inv, h, x0, j) -> (t, kv)`` runs invocation j;
    ``layer(h, t, p[, cache]) -> (h, new cache or None)`` runs one layer.
    Returns (h, the runs' SSM caches, the invocations' KV caches)."""
    x0, h, kvs, new = x, x, [], []
    for r, (_, _, j) in enumerate(hybrid_runs(cfg)):
        t = None
        if j is not None:
            t, kv = invoke(params["shared"][j % cfg.num_mem_blocks],
                           params["invocations"][j], h, x0, j)
            kvs.append(kv)

        def body(c, xs):
            h, t = c
            h, out = layer(h, t, *xs)
            # only the run's first layer is fed the block's output
            return (h, None if t is None else jnp.zeros_like(t)), out

        xs = (params["layers"][r],) + (() if caches is None else
                                       (caches[r],))
        (h, _), out = _scan(cfg, _remat(cfg, body) if remat else body,
                            (h, t), xs)
        new.append(out)
    return h, tuple(new), tuple(kvs)


# ====================================================================== params
def init_params(key, cfg: ModelConfig) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    k_embed, k_layers, k_extra, k_head = jax.random.split(key, 4)
    params: Params = {
        "embed": L.init_embedding(k_embed, cfg.padded_vocab, cfg.d_model, dtype),
        "final_norm": L.init_rmsnorm(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = L.init_linear(k_head, cfg.d_model, cfg.padded_vocab,
                                          dtype)
    fam = cfg.family
    if fam == "ssm":
        params["layers"] = jax.vmap(
            lambda k: init_ssm_block(k, cfg, dtype))(
                _split_stack(k_layers, cfg.num_layers))
    elif fam == "hybrid":
        # one stack of mamba2 layers per run between invocations
        stack = jax.vmap(lambda k: init_ssm_block(k, cfg, dtype))(
            _split_stack(k_layers, cfg.num_layers))
        params["layers"] = tuple(jax.tree.map(lambda t: t[a:b], stack)
                                 for a, b, _ in hybrid_runs(cfg))
        params["shared"] = tuple(
            init_shared_block(k, cfg, dtype)
            for k in _split_stack(k_extra, cfg.num_mem_blocks))
        params["invocations"] = tuple(
            init_invocation(k, cfg, dtype) for k in _split_stack(
                jax.random.fold_in(k_extra, 1), len(cfg.hybrid_layer_ids)))
    elif fam == "moe":
        fd = cfg.first_dense_layers
        if fd:
            params["dense_layers"] = jax.vmap(
                lambda k: init_dense_block(k, cfg, dtype, use_moe=False,
                                           d_ff=cfg.d_ff))(
                    _split_stack(k_extra, fd))
        params["layers"] = jax.vmap(
            lambda k: init_dense_block(k, cfg, dtype, use_moe=True,
                                       d_ff=cfg.d_ff))(
                _split_stack(k_layers, cfg.num_layers - fd))
    else:
        params["layers"] = jax.vmap(
            lambda k: init_dense_block(k, cfg, dtype, use_moe=False,
                                       d_ff=cfg.d_ff))(
                _split_stack(k_layers, cfg.num_layers))
    return params


# ======================================================================= cache
def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, Any]:
    """Preallocated decoding caches (stacked over layers), plus ``index``.

    GQA ``k``/``v`` are (..., B, KV, hd, max_len), sequence last, as the
    decode attention kernel reads them; MLA's ``c_kv``/``k_rope`` are
    (..., B, max_len, r). Exactly ``max_len`` positions: a caller that
    serves on the chip rounds its length to whole 128-lane tiles itself
    (``launch/serve.py``)."""
    dtype = jnp.dtype(cfg.dtype)
    fam = cfg.family

    def gqa_cache(n_layers):
        if cfg.use_mla:
            return {"c_kv": jnp.zeros((n_layers, batch, max_len,
                                       cfg.kv_lora_rank), dtype),
                    "k_rope": jnp.zeros((n_layers, batch, max_len,
                                         cfg.qk_rope_head_dim), dtype)}
        kv = (n_layers, batch, cfg.num_kv_heads, cfg.head_dim, max_len)
        return {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype)}

    def ssm_cache(n_layers):
        K, di, G, N = cfg.ssm_conv, cfg.ssm_d_inner, cfg.ssm_groups, cfg.ssm_state
        H, P = cfg.ssm_heads, cfg.ssm_head_dim
        return {"conv_x": jnp.zeros((n_layers, batch, K - 1, di), dtype),
                "conv_B": jnp.zeros((n_layers, batch, K - 1, G * N), dtype),
                "conv_C": jnp.zeros((n_layers, batch, K - 1, G * N), dtype),
                "state": jnp.zeros((n_layers, batch, H, P, N), jnp.float32)}

    cache: Dict[str, Any] = {"index": jnp.zeros((), jnp.int32)}
    if fam == "ssm":
        cache["layers"] = ssm_cache(cfg.num_layers)
    elif fam == "hybrid":
        cache["layers"] = tuple(ssm_cache(b - a)
                                for a, b, _ in hybrid_runs(cfg))
        # one KV cache per invocation, though blocks share weights
        cache["attn"] = tuple(jax.tree.map(lambda t: t[0], gqa_cache(1))
                              for _ in cfg.hybrid_layer_ids)
    elif fam == "moe" and cfg.first_dense_layers:
        cache["dense_layers"] = gqa_cache(cfg.first_dense_layers)
        cache["layers"] = gqa_cache(cfg.num_layers - cfg.first_dense_layers)
    else:
        cache["layers"] = gqa_cache(cfg.num_layers)
    return cache


# ===================================================================== forward
def _inputs_to_h(params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray]):
    if cfg.input_mode == "embeddings" and "embeds" in batch:
        x = batch["embeds"].astype(jnp.dtype(cfg.dtype))
    else:
        x = L.embed(params["embed"], batch["tokens"], cfg)
    if cfg.pos_embed == "sinusoidal":
        pos = batch["positions"]
        x = x + L.sinusoidal_pos_embed(pos, cfg.d_model, x.dtype)
    return x


def _logits(params, cfg: ModelConfig, x):
    if cfg.tie_embeddings:
        return L.unembed(params["embed"], x, cfg)
    return L.unembed(params["unembed"], x, cfg)


def forward(params: Params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray],
            *, mode: str = "train"
            ) -> Tuple[jnp.ndarray, jnp.ndarray, Optional[Dict[str, Any]]]:
    """Full-sequence forward.

    mode='train':   returns (logits (B,S,V), aux_loss, None)
    mode='prefill': returns (last-token logits (B,1,V), aux_loss, cache)
    """
    assert mode in ("train", "prefill")
    prefill = mode == "prefill"
    positions = batch["positions"]
    x = _inputs_to_h(params, cfg, batch)
    aux_total = jnp.zeros((), jnp.float32)
    caches: Dict[str, Any] = {}
    fam = cfg.family

    if fam == "ssm":
        def body(carry, lp):
            y, cache = ssm_block_full(lp, carry, cfg, return_cache=prefill)
            return y, cache
        x, cache = _scan(cfg, _remat(cfg, body), x, params["layers"])
        if prefill:
            caches["layers"] = cache

    elif fam == "hybrid":
        def invoke(shared, inv, h, x0, j):
            return shared_block(shared, inv, h, x0, cfg, positions,
                                return_kv=prefill)

        def layer(h, t, lp):
            return ssm_block_full(lp, h, cfg, return_cache=prefill, inject=t)

        x, ssm_c, kvs = _hybrid_stack(cfg, params, x, invoke, layer, None,
                                      remat=True)
        if prefill:
            caches["layers"] = ssm_c
            caches["attn"] = kvs

    else:                                   # dense / moe / audio / vlm
        fd = cfg.first_dense_layers if fam == "moe" else 0
        if fd:
            def d_body(carry, lp):
                y, kv, _ = dense_block_full(lp, carry, cfg, positions,
                                            use_moe=False, return_kv=prefill)
                return y, kv
            x, kvs = _scan(cfg, _remat(cfg, d_body), x,
                                  params["dense_layers"])
            if prefill:
                caches["dense_layers"] = _kv_dict(cfg, kvs)

        use_moe = fam == "moe"
        def body(carry, lp):
            y, aux = carry
            y, kv, a = dense_block_full(lp, y, cfg, positions,
                                        use_moe=use_moe, return_kv=prefill)
            return (y, aux + a), kv
        (x, aux_total), kvs = _scan(
            cfg, _remat(cfg, body), (x, aux_total), params["layers"])
        if prefill:
            caches["layers"] = _kv_dict(cfg, kvs)

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if prefill:
        x = x[:, -1:, :]
        caches["index"] = jnp.asarray(batch["positions"].shape[-1], jnp.int32)
    logits = _logits(params, cfg, x)
    return logits, aux_total, (caches if prefill else None)


def _kv_dict(cfg, kvs):
    if kvs is None:
        return None
    if cfg.use_mla:
        return {"c_kv": kvs[0], "k_rope": kvs[1]}
    return {"k": kvs[0], "v": kvs[1]}


# ====================================================================== decode
def decode(params: Params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray],
           cache: Dict[str, Any]
           ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """One decode step. batch: tokens (B,1) or embeds (B,1,d) + positions.

    Returns (logits (B,1,V), new_cache)."""
    index = cache["index"]
    positions = batch["positions"]
    x = _inputs_to_h(params, cfg, batch)
    new_cache: Dict[str, Any] = {"index": index + 1}
    fam = cfg.family

    if fam == "ssm":
        def body(carry, xs):
            lp, lc = xs
            y, nc = ssm_block_decode(lp, carry, cfg, lc)
            return y, nc
        x, nc = _scan(cfg, body, x, (params["layers"], cache["layers"]))
        new_cache["layers"] = nc

    elif fam == "hybrid":
        def invoke(shared, inv, h, x0, j):
            return shared_block(shared, inv, h, x0, cfg, positions,
                                kv=cache["attn"][j], index=index)

        def layer(h, t, lp, lc):
            return ssm_block_decode(lp, h, cfg, lc, inject=t)

        x, new_cache["layers"], new_cache["attn"] = _hybrid_stack(
            cfg, params, x, invoke, layer, cache["layers"], remat=False)

    else:
        fd = cfg.first_dense_layers if fam == "moe" else 0
        if fd:
            def d_body(carry, xs):
                lp, lc = xs
                y, nc = dense_block_decode(lp, carry, cfg, positions, lc, index,
                                           use_moe=False)
                return y, nc
            x, nc = _scan(cfg, d_body, x,
                                 (params["dense_layers"], cache["dense_layers"]))
            new_cache["dense_layers"] = nc
        use_moe = fam == "moe"
        def body(carry, xs):
            lp, lc = xs
            y, nc = dense_block_decode(lp, carry, cfg, positions, lc, index,
                                       use_moe=use_moe)
            return y, nc
        x, nc = _scan(cfg, body, x, (params["layers"], cache["layers"]))
        new_cache["layers"] = nc

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = _logits(params, cfg, x)
    return logits, new_cache
