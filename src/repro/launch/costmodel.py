"""Affine-in-depth cost extrapolation for the dry-run.

XLA's ``cost_analysis()`` ignores ``while``-loop trip counts, so a scanned
(production) module under-reports per-layer flops/bytes/collectives. Layer
stacks are structurally homogeneous, so every cost is affine in the stack
depth: cost(L) = fixed + L * per_layer. We compile the *unrolled* model at two
small depths and solve exactly; the scanned full-depth compile is still
performed for the memory analysis and as the deliverable artifact.

Hybrid (zamba2) is affine in the number of periods of its layer pattern: one
shared-block invocation and the mamba2 layers up to the next one (6 in
zamba2-7b, whose ids are 6, 11, 17, ..., 77). Depth is counted in whole
layers (81 / 6 = 13.5 periods), so the model's 13 invocations count as 13.5:
one invocation over, about 2.6% of the shared-block work.
"""
from __future__ import annotations

from typing import Dict, Tuple

from repro.configs.base import ModelConfig


def probe_depths(cfg: ModelConfig) -> Tuple[Dict, Dict, float, float, float]:
    """Returns (overrides_a, overrides_b, n_a, n_b, n_target) where n_* count
    the varied stack units (layers or hybrid groups)."""
    if cfg.family == "hybrid":
        # probes end just after the second and third invocations
        ids = cfg.hybrid_layer_ids
        period = ids[2] - ids[1]
        return ({"num_layers": ids[1] + 1, "hybrid_layer_ids": ids[:2],
                 "scan_layers": False},
                {"num_layers": ids[2] + 1, "hybrid_layer_ids": ids[:3],
                 "scan_layers": False},
                2.0, 3.0, cfg.num_layers / period)
    fd = cfg.first_dense_layers
    la, lb = fd + 2, fd + 4
    n_target = cfg.num_layers - fd
    return ({"num_layers": la, "scan_layers": False},
            {"num_layers": lb, "scan_layers": False},
            2.0, 4.0, float(n_target))


def extrapolate(cost_a: Dict[str, float], cost_b: Dict[str, float],
                n_a: float, n_b: float, n_target: float) -> Dict[str, float]:
    """Per-key affine extrapolation (keys missing in either side are kept)."""
    out = {}
    keys = set(cost_a) | set(cost_b)
    for k in keys:
        ca = float(cost_a.get(k, 0.0) or 0.0)
        cb = float(cost_b.get(k, 0.0) or 0.0)
        slope = (cb - ca) / (n_b - n_a)
        out[k] = max(0.0, ca + (n_target - n_a) * slope)
    return out
