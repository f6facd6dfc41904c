"""Plain Zamba2 forward (arXiv 2411.15242; HF transformers
``modeling_zamba2.py``) in numpy float64, for the tests. It imports nothing
of the system: it reads a parameter tree laid out as the system stores it
(nested dicts and tuples of arrays; the mamba2 layers as one stack per run
between invocations) and the sizes in a dict ``m``.

    x0 = embed(tokens), h = x0; for each layer l:
      if l is the j-th hybrid id, b = j mod num_mem_blocks:
        a = RMSNorm(concat(h, x0)); o = Attn_b(a) (scores * (hd / 2)^-1/2,
        rotate-half RoPE over the whole head); mm = RMSNorm(o)
        [g | u] = mm W_gu^b + (mm A_j) B_j; t = (GELU(g) u) W_down^b Lin_j
        h = h + Mamba2_l(RMSNorm_l(h + t))
      else h = h + Mamba2_l(RMSNorm_l(h))
    logits = RMSNorm(h) E^T

Mamba2 runs its recurrence one token at a time: h_t = exp(dt_t A) h_{t-1}
+ dt_t x_t B_t^T, y_t = h_t C_t + D x_t, heads of group g reading B/C group
g, then y = GroupRMSNorm(y * SiLU(z)). Norm scales are stored as w - 1.

Switches plant departures from the published block: ``one_block`` (block 0
serves every invocation), ``x0=False`` (x0 left out of the blocks' input),
``adapters=False`` (no LoRA), ``grouped_norm=False`` (the gated norm over
all channels at once).
"""
from __future__ import annotations

import math

import numpy as np


def _f(x):
    return np.asarray(x, np.float64)


def _rmsnorm(scale, x, eps, groups=1):
    xg = x.reshape(x.shape[:-1] + (groups, -1))
    xg = xg / np.sqrt(np.mean(xg * xg, axis=-1, keepdims=True) + eps)
    return xg.reshape(x.shape) * (1.0 + _f(scale))


def _silu(x):
    return x / (1.0 + np.exp(-x))


def _softplus(x):
    return np.logaddexp(0.0, x)


def _gelu(x):
    return 0.5 * x * (1.0 + np.vectorize(math.erf)(x / math.sqrt(2.0)))


def _conv(x, w, b):
    """Depthwise causal convolution: x (B, S, C), w (K, C), b (C,)."""
    K, S = w.shape[0], x.shape[1]
    xp = np.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(w[k] * xp[:, k:k + S] for k in range(K)) + b


def mamba2(p, h, m, grouped_norm=True):
    B, S, _ = h.shape
    P, G, N = m["ssm_head_dim"], m["ssm_groups"], m["ssm_state"]
    di = m["ssm_expand"] * m["d_model"]
    H = di // P
    w = {k: _f(v) for k, v in p.items() if not isinstance(v, dict)}
    lin = {k: _f(v["w"]) for k, v in p.items() if isinstance(v, dict)
           and "w" in v}
    z = h @ lin["wz"]
    x = _silu(_conv(h @ lin["wx"], w["conv_x"], w["conv_x_bias"]))
    Bm = _silu(_conv(h @ lin["wB"], w["conv_B"], w["conv_B_bias"]))
    Cm = _silu(_conv(h @ lin["wC"], w["conv_C"], w["conv_C_bias"]))
    dt = _softplus(h @ lin["wdt"] + w["dt_bias"])
    A = -np.exp(w["A_log"])
    x = x.reshape(B, S, H, P)
    group = np.arange(H) // (H // G)
    Bh = Bm.reshape(B, S, G, N)[:, :, group]            # (B, S, H, N)
    Ch = Cm.reshape(B, S, G, N)[:, :, group]
    state = np.zeros((B, H, P, N))
    y = np.zeros((B, S, H, P))
    for t in range(S):
        decay = np.exp(dt[:, t] * A)[:, :, None, None]
        state = decay * state + (dt[:, t, :, None, None] * x[:, t, :, :, None]
                                 * Bh[:, t, :, None, :])
        y[:, t] = np.einsum("bhpn,bhn->bhp", state, Ch[:, t])
    y = y + w["D"][None, None, :, None] * x
    y = _rmsnorm(p["norm"]["scale"], y.reshape(B, S, di) * _silu(z),
                 m["norm_eps"], G if grouped_norm else 1)
    return y @ lin["w_out"]


def _rope(x, theta):
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (np.arange(half) / half)
    ang = np.arange(x.shape[1])[:, None] * inv
    c, s = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return np.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def shared_block(p, inv, h, x0, m, adapters=True):
    B, S, d = h.shape
    H, KV, hd, ff = m["num_heads"], m["num_kv_heads"], m["head_dim"], \
        m["d_ff"]
    a = _rmsnorm(p["norm_in"]["scale"], np.concatenate([h, x0], -1),
                 m["norm_eps"])
    at = p["attn"]
    q = _rope((a @ _f(at["wq"]["w"])).reshape(B, S, H, hd), m["rope_theta"])
    k = _rope((a @ _f(at["wk"]["w"])).reshape(B, S, KV, hd), m["rope_theta"])
    v = (a @ _f(at["wv"]["w"])).reshape(B, S, KV, hd)
    k, v = np.repeat(k, H // KV, axis=2), np.repeat(v, H // KV, axis=2)
    s = np.einsum("bqhd,bkhd->bhqk", q, k) * (hd / 2) ** -0.5
    s = np.where(np.tril(np.ones((S, S), bool)), s, -np.inf)
    s = np.exp(s - s.max(-1, keepdims=True))
    s = s / s.sum(-1, keepdims=True)
    o = np.einsum("bhqk,bkhd->bqhd", s, v).reshape(B, S, H * hd)
    mm = _rmsnorm(p["norm_ff"]["scale"], o @ _f(at["wo"]["w"]),
                  m["norm_eps"])
    mlp = p["mlp"]
    gu = mm @ np.concatenate([_f(mlp["w_gate"]["w"]), _f(mlp["w_in"]["w"])],
                             -1)
    if adapters:
        gu = gu + (mm @ _f(inv["lora_a"]["w"])) @ _f(inv["lora_b"]["w"])
    t = (_gelu(gu[..., :ff]) * gu[..., ff:]) @ _f(mlp["w_out"]["w"])
    return t @ _f(inv["linear"]["w"])


def _unstack(tree, i):
    if isinstance(tree, dict):
        return {k: _unstack(v, i) for k, v in tree.items()}
    return tree[i]


def _layers(runs):
    """Per-layer parameter dicts from the stacks of consecutive runs."""
    out = []
    for run in runs:
        n = len(run["norm1"]["scale"])
        out += [_unstack(run, i) for i in range(n)]
    return out


def logits(params, tokens, m, *, one_block=False, x0=True, adapters=True,
           grouped_norm=True):
    """tokens (B, S) -> logits (B, S, padded vocab), float64."""
    table = _f(params["embed"]["table"])
    first = table[np.asarray(tokens)]
    h = first
    ids = list(m["hybrid_layer_ids"])
    layers = _layers(params["layers"])
    assert len(layers) == m["num_layers"]
    for i, lp in enumerate(layers):
        t = 0.0
        if i in ids:
            j = ids.index(i)
            b = 0 if one_block else j % m["num_mem_blocks"]
            t = shared_block(params["shared"][b], params["invocations"][j],
                             h, first if x0 else np.zeros_like(first), m,
                             adapters)
        inner = _rmsnorm(lp["norm1"]["scale"], h + t, m["norm_eps"])
        h = h + mamba2(lp["ssm"], inner, m, grouped_norm)
    h = _rmsnorm(params["final_norm"]["scale"], h, m["norm_eps"])
    return h @ table.T
