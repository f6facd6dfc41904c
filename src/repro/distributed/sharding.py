"""Sharding policies: param/cache/batch PartitionSpecs per architecture family.

Two policies:
  * ``tp16``  — Megatron-style tensor parallelism over the ``model`` axis
                (attn heads / ffn hidden / vocab / experts), data parallelism
                over ``data`` (and ``pod``), ZeRO-1 optimizer-state sharding.
  * ``dp_all`` — for small attention-free models (mamba2-130m): pure data
                parallelism over the flattened (data, model) axes; only the
                vocab matmuls stay tensor-parallel.

Rules are path-based: a leaf's spec is decided by its name/rank, with leading
layer-stack dims padded with None. ``kv_heads < TP`` triggers the
replicated-KV rule (standard practice instead of GSPMD padding waste).
"""
from __future__ import annotations

import re
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig

MODEL_AXIS = "model"
DATA_AXIS = "data"
POD_AXIS = "pod"


def policy_for(cfg: ModelConfig) -> str:
    return "dp_all" if cfg.family == "ssm" else "tp16"


def mesh_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(mesh.axis_names)


def batch_axes(mesh: Mesh, cfg: ModelConfig,
               global_batch: Optional[int] = None) -> Tuple[str, ...]:
    """Mesh axes the global batch is sharded over. If ``global_batch`` is
    given, axes are dropped (right to left) until the batch divides evenly —
    pjit argument shardings require exact divisibility."""
    multi_pod = POD_AXIS in mesh.axis_names
    if policy_for(cfg) == "dp_all":
        # flatten DP over data+model; pod (if present) becomes a replica axis
        # (global_batch for the assigned cells is fixed at 256 = data*model).
        axes: Tuple[str, ...] = (DATA_AXIS, MODEL_AXIS)
    else:
        axes = (POD_AXIS, DATA_AXIS) if multi_pod else (DATA_AXIS,)
    if global_batch is not None:
        while axes:
            size = 1
            for a in axes:
                size *= mesh.shape[a]
            if global_batch % size == 0:
                break
            axes = axes[:-1]
    return axes


def _tp(cfg: ModelConfig) -> Optional[str]:
    return MODEL_AXIS if policy_for(cfg) == "tp16" else None


def _kv_shardable(cfg: ModelConfig, tp_size: int) -> bool:
    # arg-level shardings demand exact divisibility (GSPMD only pads
    # intermediates); otherwise replicate KV (standard replicated-KV rule)
    return (cfg.num_kv_heads >= tp_size
            and cfg.num_kv_heads % tp_size == 0)


# ------------------------------------------------------------------ param rules
def param_spec(cfg: ModelConfig, mesh: Mesh, path: str, ndim: int) -> P:
    """Sharding spec for a parameter leaf, identified by its tree path."""
    tp = _tp(cfg)
    tp_size = mesh.shape.get(MODEL_AXIS, 1)
    kv_tp = tp if (tp and _kv_shardable(cfg, tp_size)) else None

    def pad(spec_tail: Tuple) -> P:
        return P(*((None,) * (ndim - len(spec_tail)) + tuple(spec_tail)))

    parts = path.split("/")
    name = parts[-1]
    parent = parts[-2] if len(parts) > 1 else ""
    # linear layers are dicts {w, b}: the rule owner is the enclosing name
    owner = parent if name in ("w", "b") else name
    is_bias = name == "b"

    # ---- embeddings / head --------------------------------------------------
    if name == "table":                                   # (V, d)
        return pad((MODEL_AXIS, None) if cfg.vocab_tp else (None, None))
    if owner == "unembed":                                # (d, V)
        return pad((None, MODEL_AXIS) if cfg.vocab_tp else (None, None))

    # ---- norms / scalars -----------------------------------------------------
    if name == "scale":
        if parent == "norm" and cfg.ssm_state:            # ssm gated norm (di,)
            return pad((tp,))
        return pad((None,))
    if name in ("A_log", "D", "dt_bias"):                 # (H,): tiny
        return pad((None,))

    # ---- attention (column-parallel QKV, row-parallel O; replicated-KV rule)
    if owner == "wq":
        return pad((tp,)) if is_bias else pad((None, tp))
    if owner in ("wk", "wv"):
        return pad((kv_tp,)) if is_bias else pad((None, kv_tp))
    if owner == "wo":
        return pad((None,)) if is_bias else pad((tp, None))
    if owner in ("w_dkv", "w_krope"):                     # MLA latents: small
        return pad((None, None))
    if owner in ("w_uk", "w_uv"):                         # (r, H*dim)
        return pad((None, tp))

    # ---- MoE ---------------------------------------------------------------------
    if owner == "router" or parent == "router":
        return pad((None, None))
    if parent == "moe" and name in ("w_in", "w_gate", "w_out"):
        # expert-stacked raw arrays (E, d, ff)/(E, ff, d): expert parallelism
        return pad((tp, None, None))

    # ---- dense/shared-expert MLP -----------------------------------------------------
    if owner in ("w_in", "w_gate"):                       # (d, ff)
        return pad((None, tp))
    if owner == "w_out":                                  # (ff, d)
        return pad((tp, None))

    # ---- SSM --------------------------------------------------------------------
    if owner in ("wz", "wx"):                             # (d, di)
        return pad((None, tp))
    if owner in ("wB", "wC", "wdt"):                      # small projections
        return pad((None, None))
    if name == "conv_x":                                  # (K, di)
        return pad((None, tp))
    if name in ("conv_B", "conv_C"):
        return pad((None, None))
    # note: the SSM out-projection is named w_out and correctly hits the
    # row-parallel MLP rule above ((di, d) sharded on di).

    return P(*((None,) * ndim))


def _path_str(path) -> str:
    parts = []
    for p in path:
        if isinstance(p, jax.tree_util.DictKey):
            parts.append(str(p.key))
        else:
            parts.append(str(p))
    return "/".join(parts)


def params_pspec(cfg: ModelConfig, mesh: Mesh, params) -> Any:
    """Pytree of PartitionSpec matching ``params``."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: param_spec(cfg, mesh, _path_str(path), leaf.ndim),
        params)


def params_sharding(cfg: ModelConfig, mesh: Mesh, params) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        params_pspec(cfg, mesh, params))


# ------------------------------------------------------------------- ZeRO-1
def zero1_spec(spec: P, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Extend a param spec with optimizer-state sharding over the data axis
    (ZeRO-1): shard the first free dim divisible by |data|."""
    dp = mesh.shape.get(DATA_AXIS, 1)
    entries = list(spec) + [None] * (len(shape) - len(spec))
    for i, (ax, dim) in enumerate(zip(entries, shape)):
        if ax is None and dim % dp == 0 and dim >= dp:
            entries[i] = DATA_AXIS
            return P(*entries)
    return P(*entries)


def opt_state_pspec(cfg: ModelConfig, mesh: Mesh, params) -> Any:
    base = params_pspec(cfg, mesh, params)
    return jax.tree.map(
        lambda spec, leaf: zero1_spec(spec, leaf.shape, mesh), base, params)


# ---------------------------------------------------------------- batch / cache
def batch_pspec(cfg: ModelConfig, mesh: Mesh,
                global_batch: Optional[int] = None) -> Dict[str, P]:
    """Specs for a training/prefill batch dict."""
    b = batch_axes(mesh, cfg, global_batch)
    out = {"tokens": P(b, None), "labels": P(b, None), "positions": P(b, None)}
    if cfg.rope_kind == "mrope":
        out["positions"] = P(None, b, None)
    if cfg.input_mode == "embeddings":
        out["embeds"] = P(b, None, None)
    return out


def cache_pspec(cfg: ModelConfig, mesh: Mesh, batch_size: int) -> Any:
    """Specs for the decode cache pytree (see model.init_cache).

    Batch shards over the (divisibility-reduced) DP axes; when the batch
    can't shard at all (long-context batch=1 cell), the KV *sequence* shards
    over ``data`` instead (sequence-parallel decode) and heads over model.
    """
    tp = _tp(cfg)
    tp_size = mesh.shape.get(MODEL_AXIS, 1)
    kv_tp = tp if (tp and _kv_shardable(cfg, tp_size)) else None
    axes = batch_axes(mesh, cfg, batch_size)
    seq_parallel = not axes
    bax = axes if axes else None
    sax = DATA_AXIS if seq_parallel else None

    def kv_spec(leaf_name: str) -> Tuple:
        # trailing axes; a leading layer axis, where stacked, is replicated
        if cfg.use_mla:
            # (L,B,Smax,r) / (L,B,Smax,rope_d): latent is tiny, replicate last
            return (bax, sax, None)
        return (bax, kv_tp, None, sax)          # (L,B,KV,hd,Smax)

    def spec_for(path: str, ndim: int) -> P:
        name = path.split("/")[-1]
        if name == "index":
            return P()
        if name in ("k", "v", "c_kv", "k_rope"):
            s = kv_spec(name)
            return P(*((None,) * (ndim - len(s)) + tuple(s)))
        if name == "state":        # (L,B,H,P,N)
            s = (bax, tp, None, None)
            return P(*((None,) * (ndim - len(s)) + tuple(s)))
        if name.startswith("conv_"):   # (L,B,K-1,C)
            chan = tp if name == "conv_x" else None
            s = (bax, None, chan)
            return P(*((None,) * (ndim - len(s)) + tuple(s)))
        return P(*((None,) * ndim))

    # build from a shape-only template
    from repro.models.model import init_cache
    template = jax.eval_shape(lambda: init_cache(cfg, batch_size, 8))
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: spec_for(_path_str(path), len(leaf.shape)), template)
