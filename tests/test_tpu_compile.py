"""Compile each Pallas kernel for a described TPU v5e chip at the widths the
chip smoke runs (stablelm-3b attention, mamba2-130m SSD, stablelm-3b
rmsnorm), and the SSD kernel also as the stream benchmark's scoring forward
calls it and at zamba2-7b's heads. Nothing runs: the chip's compiler
refuses here, at no chip time, what interpret mode cannot see (unaligned
blocks, unlowerable primitives, blocks that overflow VMEM).

The topology is described only inside the module fixture: one process at a
time may load the TPU library, so it must never happen at import time."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention.decode_attention import \
    decode_attention_bhd
from repro.kernels.flash_attention.flash_attention import flash_attention_bhsd
from repro.kernels.fused_rmsnorm.fused_rmsnorm import fused_rmsnorm
from repro.kernels.ssd.ssd import ssd_pallas

B, S, H, HD = 2, 2048, 32, 80                # stablelm-3b attention
SSD_H, SSD_P, SSD_N, CHUNK = 24, 64, 128, 256  # mamba2-130m
D_MODEL = 2560                               # stablelm-3b


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                                    # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent cache
    # but not read back; keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flash_attention_compiles_for_v5e(one_chip):
    bf = jnp.bfloat16
    txt = _compile_text(
        lambda q, k, v: flash_attention_bhsd(q, k, v, scale=HD ** -0.5),
        one_chip, ((B, H, S, HD), bf), ((B, H, S, HD), bf),
        ((B, H, S, HD), bf))
    assert "tpu_custom_call" in txt


def test_decode_attention_compiles_for_v5e(one_chip):
    bf = jnp.bfloat16
    txt = _compile_text(
        lambda q, k, v, n: decode_attention_bhd(q, k, v, n,
                                                scale=HD ** -0.5),
        one_chip, ((B, H, 1, HD), bf), ((B, H, S, HD), bf),
        ((B, H, S, HD), bf), ((), jnp.int32))
    assert "tpu_custom_call" in txt


def test_ssd_compiles_for_v5e(one_chip):
    bf, f32 = jnp.bfloat16, jnp.float32
    txt = _compile_text(
        lambda x, dt, a, b, c: ssd_pallas(x, dt, a, b, c, chunk=CHUNK),
        one_chip, ((B, S, SSD_H, SSD_P), bf), ((B, S, SSD_H), f32),
        ((SSD_H,), f32), ((B, S, 1, SSD_N), bf), ((B, S, 1, SSD_N), bf))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("b,s,h,n,return_state", [
    (1, 256, SSD_H, SSD_N, False),   # a 1 x 256 scoring task, no state
    (1, 2048, 112, 64, True),        # zamba2-7b: 112 heads of state 64
])
def test_ssd_head_blocks_compile_for_v5e(one_chip, b, s, h, n, return_state):
    """A head block whose blocks and temporaries overflow VMEM, or whose
    lanes the chip cannot tile, is refused here."""
    bf, f32 = jnp.bfloat16, jnp.float32
    txt = _compile_text(
        lambda x, dt, a, bm, cm: ssd_pallas(x, dt, a, bm, cm, chunk=CHUNK,
                                            return_state=return_state),
        one_chip, ((b, s, h, SSD_P), bf), ((b, s, h), f32), ((h,), f32),
        ((b, s, 1, n), bf), ((b, s, 1, n), bf))
    assert "tpu_custom_call" in txt


def test_fused_rmsnorm_compiles_for_v5e(one_chip):
    bf = jnp.bfloat16
    txt = _compile_text(lambda x, w: fused_rmsnorm(x, w), one_chip,
                        ((B * S, D_MODEL), bf), ((D_MODEL,), bf))
    assert "tpu_custom_call" in txt
