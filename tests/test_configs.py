"""Config registry: published sizes, divisibility, shape-cell rules."""
import pytest

from repro.configs import ARCH_IDS, SHAPES, all_configs, cell_is_runnable, \
    get_config, get_smoke_config

# published parameter counts (billions), generous tolerance for the
# backbone-only stubs (musicgen: no text cross-attn; qwen2-vl: no ViT)
PUBLISHED_B = {
    "mamba2-130m": (0.13, 0.15),
    "phi3.5-moe-42b-a6.6b": (41.9, 0.1),
    "deepseek-v2-lite-16b": (15.7, 0.1),
    "musicgen-medium": (1.4, 0.25),
    "zamba2-7b": (7.4, 0.05),        # 2 shared blocks, 13 invocations
    "chatglm3-6b": (6.2, 0.1),
    "stablelm-3b": (2.8, 0.1),
    "gemma-7b": (8.5, 0.1),
    "stablelm-12b": (12.1, 0.1),
    "qwen2-vl-7b": (7.6, 0.1),
}


def test_registry_has_all_ten():
    assert len(ARCH_IDS) == 10
    assert len(all_configs()) == 10


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_counts_match_published(arch):
    cfg = get_config(arch)
    n = cfg.num_params() / 1e9
    want, tol = PUBLISHED_B[arch]
    assert abs(n - want) / want < tol, f"{arch}: {n:.3f}B vs {want}B"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_padded_vocab_divisible_by_tp(arch):
    cfg = get_config(arch)
    assert cfg.padded_vocab % 16 == 0
    assert cfg.padded_vocab >= cfg.vocab_size


def test_active_params_moe():
    phi = get_config("phi3.5-moe-42b-a6.6b")
    assert 6.0e9 < phi.num_active_params() < 7.5e9        # "a6.6b"
    ds = get_config("deepseek-v2-lite-16b")
    assert ds.num_active_params() < ds.num_params() / 3


def test_long_context_cell_rules():
    runnable = {a: cell_is_runnable(get_config(a), SHAPES["long_500k"])[0]
                for a in ARCH_IDS}
    assert runnable["mamba2-130m"] and runnable["zamba2-7b"]
    assert sum(runnable.values()) == 2                     # only sub-quadratic
    for a in ARCH_IDS:                                     # all other shapes run
        for s in ("train_4k", "prefill_32k", "decode_32k"):
            assert cell_is_runnable(get_config(a), SHAPES[s])[0]


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_config_is_small(arch):
    cfg = get_smoke_config(arch)
    assert cfg.num_params() < 5e6
    assert cfg.family == get_config(arch).family
