"""zamba2-7b — Mamba2 backbone with two alternating weight-shared attention
blocks. [arXiv:2411.15242; https://huggingface.co/Zyphra/Zamba2-7B-Instruct]

Layer equations, d = 3584. x0 = embed(tokens), h = x0. For each layer l:

* if l is the j-th id of ``hybrid_layer_ids``, with b = j mod
  ``num_mem_blocks`` (2):

    a = RMSNorm_b,in(concat(h, x0))                 width 2d = 7168
    q, k, v = a W_{q,k,v}^b                         7168 -> 32 x 224, RoPE
                                                    (theta 10000) on q, k
    o = causal-softmax(q k^T (224/2)^-1/2) v W_o^b  7168 -> 3584
    m = RMSNorm_b,ff(o)
    [g | u] = m W_gu^b + (m A_j) B_j                W_gu 3584 -> 2 x 14336,
                                                    A_j 3584 -> 128 (rank),
                                                    B_j 128 -> 2 x 14336
    t = (GELU(g) * u) W_down^b Lin_j                Lin_j 3584 x 3584
    h = h + Mamba2_l(RMSNorm_l(h + t))

* otherwise h = h + Mamba2_l(RMSNorm_l(h)).

The shared block has no residual of its own: its output t joins the input
of that layer's Mamba2 mixer, not the residual stream. Mamba2_l: in_proj to
z (7168), x (7168), B and C (2 groups x 64 each) and dt (112 heads); a causal
convolution (K 4) with bias on x, B and C, then SiLU; SSD with heads 0-55
reading group 0 and 56-111 group 1; y = GroupRMSNorm_2(y * SiLU(z)) (each
group of 3584 channels normalized on its own), then out_proj.
Logits = RMSNorm_f(h) E^T (tied embedding).

From the reference implementation (HF transformers ``modeling_zamba2.py``),
not from ``config.json``: the score scale (224/2)^-1/2, the gated norm's
group size d_inner / groups, tied embeddings, and GELU in its erf form
(``hidden_act`` "gelu").
"""
from .base import ModelConfig

CONFIG = ModelConfig(
    name="zamba2-7b",
    family="hybrid",
    num_layers=81,
    d_model=3584,
    num_heads=32,
    num_kv_heads=32,
    head_dim=224,
    d_ff=14336,
    act="gelu_exact",
    vocab_size=32000,
    ssm_state=64,
    ssm_head_dim=64,
    ssm_expand=2,
    ssm_conv=4,
    ssm_chunk=256,
    ssm_groups=2,
    ssm_conv_bias=True,
    hybrid_layer_ids=(6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77),
    num_mem_blocks=2,
    adapter_rank=128,
    tie_embeddings=True,
    rope_theta=10000.0,
)
