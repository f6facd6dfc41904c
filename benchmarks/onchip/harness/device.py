"""The device a run holds, its published peaks, and its memory peak.

A run on any platform other than a TPU, or with fewer chips than the cell
asks for, is refused: no number is ever measured on a stand-in."""
from __future__ import annotations

from typing import Any, Dict, List

# Published peaks per chip, keyed by JAX's ``device_kind``. Source: Google
# Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of
# HBM at 819 GB/s.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


class DeviceError(RuntimeError):
    pass


def peak(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peak for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def claim(chips: int, require_tpu: bool = True) -> List[Any]:
    """The first ``chips`` devices; raises unless they are TPUs (when
    ``require_tpu``) and there are enough of them."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and platform != "tpu":
        raise DeviceError(f"needs a TPU, JAX found platform {platform!r}")
    if len(devices) < chips:
        raise DeviceError(f"the cell needs {chips} chips, JAX found "
                          f"{len(devices)}")
    return devices[:chips]


def describe(devices) -> Dict[str, Any]:
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices`` (0 where the backend
    keeps no statistics)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0
