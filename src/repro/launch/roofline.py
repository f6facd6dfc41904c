"""Roofline-term derivation from compiled dry-run artifacts.

Target hardware: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.

The compiled module is the per-device SPMD program, so ``cost_analysis()``
flops/bytes and HLO shapes are already per-device:
    compute    = flops_dev / peak
    memory     = bytes_dev / hbm_bw
    collective = collective_bytes_dev / link_bw
(equal to the global/(chips * bw) formulation). collective_bytes sums the
result-shape bytes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute instruction (ring-traffic approximation,
documented in EXPERIMENTS.md §Roofline).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, asdict
from typing import Dict, Optional

from repro.configs.base import ModelConfig, ShapeConfig

PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # B/s per chip
ICI_BW = 50e9                # B/s per link

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """Sum bytes over every typed array in a (possibly tuple) shape string."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def parse_collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-collective-kind result bytes from (compiled) HLO text."""
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        s = line.strip()
        if not s.startswith("%") and " = " not in s:
            continue
        for kind in _COLLECTIVES:
            # match op name with optional -start suffix; skip -done (same buf)
            token = f" {kind}("
            token_start = f" {kind}-start("
            if token in s or token_start in s:
                lhs = s.split(" = ", 1)
                if len(lhs) != 2:
                    continue
                shape_part = lhs[1].split(kind, 1)[0]
                b = _shape_bytes(shape_part)
                out[kind] += b
                out["count"] += 1
                break
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


@dataclass
class RooflineTerms:
    arch: str
    shape: str
    mesh: str
    n_devices: int
    flops_dev: float
    bytes_dev: float
    collective_bytes_dev: float
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops: float
    useful_ratio: float                 # MODEL_FLOPS / (HLO flops global)
    step_time_s: float                  # max of the three terms
    hw_frac: float                      # roofline fraction achieved (model
                                        # flops / (step_time * chips * peak))
    peak_bytes_dev: Optional[float] = None

    def to_dict(self):
        return asdict(self)


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """Useful flops per step: 6*N_active*D for train, 2*N_active*D forward
    (+ attention-cache term for decode)."""
    D = shape.global_batch * shape.seq_len
    N = cfg.num_active_params()
    if shape.kind == "train":
        return 6.0 * N * D
    if shape.kind == "prefill":
        attn = 0.0
        if cfg.num_heads:
            qk_dim = ((cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
                      if cfg.use_mla else cfg.head_dim)
            n_attn = (cfg.num_layers if cfg.family != "hybrid"
                      else len(cfg.hybrid_layer_ids))
            # causal: S^2/2 per pair of matmuls (QK^T, AV)
            attn = (2.0 * 2.0 * cfg.num_heads * qk_dim
                    * shape.seq_len ** 2 / 2 * shape.global_batch * n_attn)
        return 2.0 * N * D + attn
    # decode: one token per sequence + attention against the cache
    toks = shape.global_batch
    attn = 0.0
    if cfg.num_heads:
        qk_dim = ((cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
                  if cfg.use_mla else cfg.head_dim)
        n_attn = (cfg.num_layers if cfg.family != "hybrid"
                  else len(cfg.hybrid_layer_ids))
        attn = 2.0 * 2.0 * cfg.num_heads * qk_dim * shape.seq_len * toks * n_attn
    ssm = 0.0
    if cfg.ssm_state:
        # state update + readout: 2 * H*P*N madds each
        ssm = (2.0 * 2.0 * cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state
               * toks * cfg.num_layers)
    return 2.0 * N * toks + attn + ssm


def derive(arch: str, shape_cfg: ShapeConfig, cfg: ModelConfig, mesh_name: str,
           n_devices: int, cost: Dict[str, float], coll: Dict[str, int],
           peak_bytes_dev: Optional[float] = None) -> RooflineTerms:
    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev = float(cost.get("bytes accessed", 0.0))
    coll_dev = float(coll.get("total", 0))
    compute_s = flops_dev / PEAK_FLOPS
    memory_s = bytes_dev / HBM_BW
    collective_s = coll_dev / ICI_BW
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    bottleneck = max(terms, key=terms.get)
    mf = model_flops(cfg, shape_cfg)
    hlo_global = flops_dev * n_devices
    step = max(compute_s, memory_s, collective_s)
    return RooflineTerms(
        arch=arch, shape=shape_cfg.name, mesh=mesh_name, n_devices=n_devices,
        flops_dev=flops_dev, bytes_dev=bytes_dev,
        collective_bytes_dev=coll_dev,
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        bottleneck=bottleneck, model_flops=mf,
        useful_ratio=(mf / hlo_global if hlo_global else 0.0),
        step_time_s=step,
        hw_frac=(mf / (step * n_devices * PEAK_FLOPS) if step else 0.0),
        peak_bytes_dev=peak_bytes_dev)
