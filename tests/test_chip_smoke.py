"""CPU rehearsal of chip_smoke.py: the same campaign, kernel and partition
functions the chip runs, at reduced configs with interpret-mode kernels.
The test steers the size; the script itself has no such option."""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np

from repro.configs import get_smoke_config

ROOT = Path(__file__).resolve().parents[1]
SMOKE = ROOT / "chip_smoke.py"


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_campaign_on_cpu():
    cs = _load_smoke()
    out = cs.run_campaign(get_smoke_config("mamba2-130m"),
                          get_smoke_config("stablelm-3b", use_pallas=True),
                          n_docking=64, train_batch=2, train_seq=64,
                          n_prompts=2, prompt_len=32, new_tokens=4,
                          interpret=True)
    assert len(out["docking"]) == 64
    assert len(out["losses"]) == 3 and np.isfinite(out["losses"]).all()
    assert out["inference"]["tokens"].shape == (2, 36)
    assert set(out["times"]) == {"docking", "sst_train", "inference"}


def test_smoke_kernels_interpret_small():
    cs = _load_smoke()
    small = dict(batch=1, seq=256, heads=2, head_dim=80, ssd_heads=2,
                 ssd_p=64, ssd_n=128, chunk=128, d_model=256)
    errs = cs.run_kernels(small, interpret=True)
    assert set(errs) == {"flash_attention", "decode_attention", "ssd",
                         "fused_rmsnorm"}
    assert max(errs.values()) <= cs.KERNEL_TOL


_PARTITIONS = """
import importlib.util, json, sys
spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
cs = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cs)
from repro.configs import get_smoke_config
out = cs.run_partitions(get_smoke_config("mamba2-130m"), global_batch=8,
                        seq_len=64)
with open("/proc/self/maps") as f:
    libtpu = "libtpu" in f.read()
print(json.dumps({"parts": [p["mesh_devices"] for p in out["partitions"]],
                  "diff": out["max_diff"], "libtpu": libtpu}))
"""


def test_smoke_partitions_on_four_virtual_devices():
    """Two flux partitions over 4 virtual CPU devices. A subprocess: the
    device count is fixed when JAX starts, so XLA_FLAGS precedes import."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    proc = subprocess.run([sys.executable, "-c", _PARTITIONS, str(SMOKE)],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["parts"] == [[0, 1], [2, 3]]
    assert res["diff"] <= 2e-2
    assert not res["libtpu"]


def test_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(SMOKE)], capture_output=True,
                          text=True, env=env, timeout=300)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout
