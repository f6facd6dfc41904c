"""Plain Mamba2 (SSD) language model: arXiv 2405.21060, in float32.

The selective state-space layer is computed in its quadratic "dual" form
(the paper's §5): y_i = sum_{j<=i} C_i.B_j exp(sum_{k=j+1..i} dt_k A) dt_j x_j
over the whole sequence, one batch row at a time. No chunking, no kernels,
no cache. Projections to z, x, B, C and dt are separate matrices and x, B, C
have separate depthwise causal convolutions, which is the same mathematics
as the fused projection and convolution of the published model. Parameters
are stored in the configuration's type and computed with in float32.

``m`` is the model's entry of a configuration file (its sizes).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import common as C
from .numerics import dot, round_to, through


def dims(m):
    d = m["d_model"]
    di = m["ssm_expand"] * d
    H = di // m["ssm_head_dim"]
    return d, di, H, m["ssm_head_dim"], m["ssm_groups"], m["ssm_state"], \
        m["ssm_conv"]


def _layer_init(key, m):
    d, di, H, P, G, N, K = dims(m)
    dtype = m["dtype"]
    ks = jax.random.split(key, 10)
    dt0 = jnp.exp(jax.random.uniform(ks[0], (H,), minval=math.log(1e-3),
                                     maxval=math.log(0.1)))
    std_conv = 1.0 / math.sqrt(K)

    def conv(k, c):
        return through(std_conv * jax.random.normal(k, (K, c)),
                       jnp.dtype(dtype))

    ssm = {
        "wz": C.linear_init(ks[2], d, di, dtype),
        "wx": C.linear_init(ks[3], d, di, dtype),
        "wB": C.linear_init(ks[4], d, G * N, dtype),
        "wC": C.linear_init(ks[5], d, G * N, dtype),
        "wdt": C.linear_init(ks[6], d, H, dtype),
        "conv_x": conv(ks[7], di),
        "conv_B": conv(ks[8], G * N),
        "conv_C": conv(ks[9], G * N),
        "A_log": jnp.log(jax.random.uniform(ks[1], (H,), minval=1.0,
                                            maxval=16.0)),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
        "norm": C.norm_init(di),
        "w_out": C.linear_init(jax.random.fold_in(key, 99), di, d, dtype,
                               std=1.0 / math.sqrt(di * 2 * m["num_layers"])),
    }
    return {"norm1": C.norm_init(d), "ssm": ssm}


def init(key, m):
    """Float32 parameters, stacked over layers, from ``jax.random.PRNGKey``
    of the seed."""
    k_embed, k_layers, _, _ = jax.random.split(key, 4)
    d = m["d_model"]
    return {
        "embed": {"table": C.trunc_normal(k_embed, (C.padded_vocab(m), d),
                                          1.0 / math.sqrt(d), m["dtype"])},
        "final_norm": C.norm_init(d),
        "layers": jax.vmap(lambda k: _layer_init(k, m))(
            jax.random.split(k_layers, m["num_layers"])),
    }


def _causal_conv(x, w):
    """Depthwise causal convolution: x (B, S, C), w (K, C)."""
    K, S = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(w[k] * xp[:, k:k + S] for k in range(K))


def _ssd_row(x, dt, A, Bm, Cm):
    """One row in the dual form. x (S,H,P), dt (S,H), Bm/Cm (S,G,N)."""
    S, H, _ = x.shape
    G = Bm.shape[1]
    cum = jnp.cumsum(dt * A, axis=0)                       # (S, H)
    seg = cum[:, None, :] - cum[None, :, :]                 # (i, j, H)
    causal = jnp.tril(jnp.ones((S, S), bool))[:, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))       # masked first
    cb = jnp.einsum("ign,jgn->ijg", Cm, Bm,
                    precision=jax.lax.Precision.HIGHEST)
    cb = jnp.repeat(cb, H // G, axis=2)                     # (i, j, H)
    w = decay * cb * dt[None, :, :]
    return jnp.einsum("ijh,jhp->ihp", w, x,
                      precision=jax.lax.Precision.HIGHEST)


def _mixer(p, h, m, prec):
    d, di, H, P, G, N, K = dims(m)
    B, S, _ = h.shape
    z = dot(h, p["wz"]["w"], prec)
    xi = jax.nn.silu(_causal_conv(dot(h, p["wx"]["w"], prec), p["conv_x"]))
    Bi = jax.nn.silu(_causal_conv(dot(h, p["wB"]["w"], prec), p["conv_B"]))
    Ci = jax.nn.silu(_causal_conv(dot(h, p["wC"]["w"], prec), p["conv_C"]))
    dt = jax.nn.softplus(dot(h, p["wdt"]["w"], prec) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    x4 = xi.reshape(B, S, H, P)
    y = jax.lax.map(lambda r: _ssd_row(r[0], r[1], A, r[2], r[3]),
                    (x4, dt, Bi.reshape(B, S, G, N), Ci.reshape(B, S, G, N)))
    y = y + p["D"][None, None, :, None] * x4
    y = C.rmsnorm(p["norm"], y.reshape(B, S, di) * jax.nn.silu(z),
                  m["norm_eps"])
    return dot(y, p["w_out"]["w"], prec)


def logits(params, tokens, m, prec="f32"):
    """tokens (B, S) -> logits (B, S, padded vocab), float32."""
    x = params["embed"]["table"][tokens]

    @jax.checkpoint
    def layer(x, lp):
        h = C.rmsnorm(lp["norm1"], x, m["norm_eps"])
        return x + _mixer(lp["ssm"], h, m, prec), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = C.rmsnorm(params["final_norm"], x, m["norm_eps"])
    return dot(x, params["embed"]["table"].T, prec)


def mean_logprob(lg, tokens):
    """Mean log-probability of tokens[:, 1:] given their prefixes, per row."""
    lp = jax.nn.log_softmax(lg[:, :-1], axis=-1)
    got = jnp.take_along_axis(lp, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(got, axis=-1)


def loss(params, batch, m, prec="f32"):
    """Cross-entropy of labels over the padded vocabulary, mean over tokens."""
    lg = logits(params, batch["tokens"], m, prec)
    lse = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, batch["labels"][..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


def round_params(params, m):
    """Parameters as the configuration stores them: matrices, convolutions,
    norms and the embedding in its type, A_log, D and dt_bias in float32."""
    f32 = {"A_log", "D", "dt_bias"}

    def one(path, x):
        name = str(path[-1].key)
        return x if name in f32 else round_to(x, m["dtype"])

    return jax.tree_util.tree_map_with_path(one, params)

