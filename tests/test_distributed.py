"""Distribution layer: sharding-rule divisibility for every arch on the
production mesh (via AbstractMesh — no devices needed), ZeRO-1 spec behavior,
int8 compression math, sharded train step on the host mesh."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from conftest import make_batch
from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.distributed import sharding as SH
from repro.distributed.compression import int8_psum_mean, quantize_int8
from repro.launch import specs as SP

MESHES = {
    "single_pod": AbstractMesh((16, 16), ("data", "model")),
    "multi_pod": AbstractMesh((2, 16, 16), ("pod", "data", "model")),
}


def _check_divisible(tree_sds, tree_spec, mesh, where):
    flat_s = jax.tree.leaves(tree_sds)
    flat_p = jax.tree.leaves(tree_spec, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_s) == len(flat_p)
    for sds, spec in zip(flat_s, flat_p):
        for dim, ax in zip(sds.shape, tuple(spec) + (None,) * 10):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            size = 1
            for a in axes:
                size *= mesh.shape[a]
            assert dim % size == 0, \
                f"{where}: dim {dim} not divisible by {axes} ({size})"


@pytest.mark.parametrize("mesh_name", list(MESHES))
@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_and_opt_specs_divisible(arch, mesh_name):
    cfg = get_config(arch)
    mesh = MESHES[mesh_name]
    params = SP.params_struct(cfg)
    spec = SH.params_pspec(cfg, mesh, params)
    _check_divisible(params, spec, mesh, f"{arch} params")
    opt = SP.opt_state_struct(params)
    ospec = SH.opt_state_pspec(cfg, mesh, opt)
    _check_divisible(opt, ospec, mesh, f"{arch} opt")


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_cache_specs_divisible(arch):
    from repro.configs import SHAPES, cell_is_runnable
    from repro.models.model import init_cache
    cfg = get_config(arch)
    mesh = MESHES["single_pod"]
    for shape_name in ("decode_32k", "long_500k"):
        shape = SHAPES[shape_name]
        if not cell_is_runnable(cfg, shape)[0]:
            continue
        cache = jax.eval_shape(
            lambda: init_cache(cfg, shape.global_batch, shape.seq_len))
        spec = SH.cache_pspec(cfg, mesh, shape.global_batch)
        _check_divisible(cache, spec, mesh, f"{arch} {shape_name} cache")


def test_batch_axes_divisibility_fallback():
    cfg = get_config("mamba2-130m")                   # dp_all policy
    mesh = MESHES["single_pod"]
    assert SH.batch_axes(mesh, cfg, 256) == ("data", "model")
    assert SH.batch_axes(mesh, cfg, 32) == ("data",)  # 32 % 256 != 0
    assert SH.batch_axes(mesh, cfg, 1) == ()
    dense = get_config("gemma-7b")
    assert SH.batch_axes(MESHES["multi_pod"], dense, 256) == ("pod", "data")


def test_replicated_kv_rule():
    mesh = MESHES["single_pod"]
    # chatglm kv=2 < 16 -> replicated; zamba kv=32 -> sharded
    chat = get_config("chatglm3-6b")
    spec = SH.param_spec(chat, mesh, "layers/attn/wk/w", 3)
    assert tuple(spec) in ((None, None, None), (None, None)) or \
        spec[-1] is None
    zam = get_config("zamba2-7b")
    spec = SH.param_spec(zam, mesh, "shared/[0]/attn/wk/w", 2)
    assert spec[-1] == "model"
    # musicgen kv=24: not divisible by 16 -> replicated (arg-level rule)
    mg = get_config("musicgen-medium")
    spec = SH.param_spec(mg, mesh, "layers/attn/wk/w", 3)
    assert spec[-1] is None


def test_zero1_shards_over_data():
    mesh = MESHES["single_pod"]
    spec = SH.zero1_spec(P(None, "model"), (4096, 1024), mesh)
    assert tuple(spec) == ("data", "model")
    # indivisible first dim -> untouched
    spec = SH.zero1_spec(P(None,), (27,), mesh)
    assert tuple(spec) == (None,)


def test_expert_weights_expert_parallel():
    mesh = MESHES["single_pod"]
    cfg = get_config("deepseek-v2-lite-16b")
    spec = SH.param_spec(cfg, mesh, "layers/moe/w_in", 4)   # (L, E, d, ff)
    assert tuple(spec) == (None, "model", None, None)
    # dense-mlp w inside moe arch must NOT hit the expert rule
    spec = SH.param_spec(cfg, mesh, "dense_layers/mlp/w_gate/w", 3)
    assert tuple(spec) == (None, None, "model")


# ----------------------------------------------------------- int8 compression
def test_quantize_int8_error_bound():
    x = jnp.asarray(np.random.RandomState(0).randn(1000), jnp.float32)
    scale = jnp.max(jnp.abs(x)) / 127.0
    q = quantize_int8(x, scale)
    err = jnp.max(jnp.abs(q.astype(jnp.float32) * scale - x))
    assert float(err) <= float(scale) * 0.5 + 1e-7


def test_int8_psum_mean_single_shard():
    mesh = jax.make_mesh((1,), ("data",))
    from functools import partial
    x = jnp.asarray(np.random.RandomState(1).randn(64), jnp.float32)

    @partial(jax.shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
             check_vma=False)
    def f(v):
        return int8_psum_mean(v, ("data",), 1)

    out = f(x)
    assert float(jnp.max(jnp.abs(out - x))) < float(
        jnp.max(jnp.abs(x))) / 127.0 + 1e-7


def test_local_grad_fn_matches_plain_grads():
    """On a 1-device mesh the compressed local-grad path must equal plain
    grads up to int8 quantization error."""
    from repro.distributed.compression import make_local_grad_fn
    from repro.distributed.train_step import make_loss_fn
    from repro.models import model as M
    cfg = get_smoke_config("stablelm-3b", dtype="float32")
    key = jax.random.PRNGKey(0)
    params = M.init_params(key, cfg)
    batch = make_batch(cfg, key, 2, 8)
    loss_fn = make_loss_fn(cfg)
    g_plain, _ = jax.grad(loss_fn, has_aux=True)(params, batch)
    mesh = jax.make_mesh((1,), ("data",))
    local = make_local_grad_fn(loss_fn, mesh, ("data",), {}, compress=True)
    g_comp, _ = local(params, batch)
    for a, b in zip(jax.tree.leaves(g_plain), jax.tree.leaves(g_comp)):
        scale = float(jnp.max(jnp.abs(a))) / 127.0
        assert float(jnp.max(jnp.abs(a.astype(jnp.float32) - b))) <= \
            scale + 1e-6


# -------------------------------------------------------- sharded train (host)
def test_train_step_on_host_mesh():
    from repro.launch.train import train
    cfg = get_smoke_config("chatglm3-6b")
    out = train(cfg, steps=3, global_batch=2, seq_len=16, quiet=True)
    assert np.isfinite(out["final_loss"])
