"""Compile each Pallas kernel for a described TPU v5e chip at the widths the
chip smoke runs (stablelm-3b attention, mamba2-130m SSD, stablelm-3b
rmsnorm), and the SSD kernel also as the stream benchmark's scoring forward
calls it and at zamba2-7b's heads and groups, and flash and decode
attention at zamba2-7b's head width (224, not a multiple of 128 lanes).
Nothing runs: the chip's compiler
refuses here, at no chip time, what interpret mode cannot see (unaligned
blocks, unlowerable primitives, blocks that overflow VMEM).

The topology is described only inside the module fixture: one process at a
time may load the TPU library, so it must never happen at import time."""
import math
import re

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
        one_chip, ((B, H, 1, HD), bf), ((B, H, HD, S), bf),
        ((B, H, HD, S), bf), ((), jnp.int32))
    assert "tpu_custom_call" in txt


def test_ssd_compiles_for_v5e(one_chip):
    bf, f32 = jnp.bfloat16, jnp.float32
    txt = _compile_text(
        lambda x, dt, a, b, c: ssd_pallas(x, dt, a, b, c, chunk=CHUNK),
        one_chip, ((B, S, SSD_H, SSD_P), bf), ((B, S, SSD_H), f32),
        ((SSD_H,), f32), ((B, S, 1, SSD_N), bf), ((B, S, 1, SSD_N), bf))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("b,s,h,g,n,return_state", [
    (1, 256, SSD_H, 1, SSD_N, False),   # a 1 x 256 scoring task, no state
    (4, 1024, 112, 2, 64, True),        # zamba2-7b: 112 heads, 2 groups of
])                                      # state 64, as generate.zamba2 runs
def test_ssd_head_blocks_compile_for_v5e(one_chip, b, s, h, g, n,
                                         return_state):
    """A head block whose blocks and temporaries overflow VMEM, or whose
    lanes the chip cannot tile, is refused here."""
    bf, f32 = jnp.bfloat16, jnp.float32
    txt = _compile_text(
        lambda x, dt, a, bm, cm: ssd_pallas(x, dt, a, bm, cm, chunk=CHUNK,
                                            return_state=return_state),
        one_chip, ((b, s, h, SSD_P), bf), ((b, s, h), f32), ((h,), f32),
        ((b, s, g, n), bf), ((b, s, g, n), bf))
    assert "tpu_custom_call" in txt


# zamba2-7b's shared attention as generate.zamba2 runs it: 4 x 1024 prompts,
# 32 heads of 224, a cache of 1024 + 32 rounded to whole 128-lane tiles
Z_B, Z_S, Z_H, Z_HD, Z_CACHE = 4, 1024, 32, 224, 1152


def test_flash_attention_at_head_dim_224_compiles_for_v5e(one_chip):
    bf = jnp.bfloat16
    txt = _compile_text(
        lambda q, k, v: flash_attention_bhsd(q, k, v,
                                             scale=(Z_HD / 2) ** -0.5),
        one_chip, ((Z_B, Z_H, Z_S, Z_HD), bf), ((Z_B, Z_H, Z_S, Z_HD), bf),
        ((Z_B, Z_H, Z_S, Z_HD), bf))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("cache", [Z_CACHE, 1056])   # 1056: one whole block
def test_decode_attention_at_head_dim_224_compiles_for_v5e(one_chip, cache):
    bf = jnp.bfloat16
    txt = _compile_text(
        lambda q, k, v, n: decode_attention_bhd(q, k, v, n,
                                                scale=(Z_HD / 2) ** -0.5),
        one_chip, ((Z_B, Z_H, 1, Z_HD), bf), ((Z_B, Z_H, Z_HD, cache), bf),
        ((Z_B, Z_H, Z_HD, cache), bf), ((), jnp.int32))
    assert "tpu_custom_call" in txt


def _kv_leaves(args):
    """Flat argument numbers of the GQA k/v cache leaves among ``args``."""
    flat = jax.tree_util.tree_flatten_with_path(args)[0]
    return [i for i, (path, _) in enumerate(flat)
            if getattr(path[-1], "key", None) in ("k", "v")]


def _layer_cache_relayouts(txt, b, kv, hd, s):
    """The copies, pads and transposes in the HLO whose result is one
    layer's KV cache: b, kv and hd among its dimensions, and at least ``s``
    but fewer than 2 ``s`` positions in the rest (a stack of layers is
    another thing)."""
    found = []
    for m in re.finditer(r"= \w+\[([\d,]+)\]\S* (copy|copy-start|pad|"
                         r"transpose)\(", txt):
        rest = [int(d) for d in m.group(1).split(",")]
        for d in (b, kv, hd):
            if d not in rest:
                break
            rest.remove(d)
        else:
            if s <= math.prod(rest) < 2 * s:
                found.append(m.group(0))
    return found


@pytest.mark.parametrize("arch,overrides,cache", [
    # generate.zamba2's stage: 27 layers, one KV cache per invocation,
    # 1024 + 32 positions rounded to whole 128-lane tiles
    ("zamba2-7b", {"num_layers": 27, "hybrid_layer_ids": (6, 11, 17, 23)},
     1152),
    ("stablelm-3b", {}, 640),     # the held campaign's 512 + 16, rounded
])
def test_decode_step_reads_kv_cache_in_place_on_v5e(one_chip, arch,
                                                    overrides, cache):
    """The whole decode step at the cell's sizes, the cache donated as
    ``launch.serve`` donates it: no layer's KV cache is copied, padded or
    transposed around the decode attention kernel, and every k/v cache
    parameter is aliased to its output (the new column is written in
    place)."""
    from repro.configs import get_config
    from repro.distributed.serve_step import make_decode_step
    from repro.models import model as M
    cfg = get_config(arch, use_pallas=True, **overrides)
    b = 4

    def placed(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = placed(jax.eval_shape(
        lambda: M.init_params(jax.random.PRNGKey(0), cfg)))
    kv = placed(jax.eval_shape(lambda: M.init_cache(cfg, b, cache)))
    batch = placed({"tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32),
                    "positions": jax.ShapeDtypeStruct((b, 1), jnp.int32)})
    txt = jax.jit(make_decode_step(cfg), donate_argnums=(2,)).lower(
        params, batch, kv).compile().as_text()
    assert "tpu_custom_call" in txt
    assert _layer_cache_relayouts(txt, b, cfg.num_kv_heads, cfg.head_dim,
                                  cache) == []
    alias = re.search(r"input_output_alias=\{(.*?)\}, entry", txt).group(1)
    aliased = {int(p) for p in re.findall(r"\((\d+), \{\}", alias)}
    kv_args = _kv_leaves((params, batch, kv))
    assert kv_args and set(kv_args) <= aliased


def test_fused_rmsnorm_compiles_for_v5e(one_chip):
    bf = jnp.bfloat16
    txt = _compile_text(lambda x, w: fused_rmsnorm(x, w), one_chip,
                        ((B * S, D_MODEL), bf), ((D_MODEL,), bf))
    assert "tpu_custom_call" in txt
