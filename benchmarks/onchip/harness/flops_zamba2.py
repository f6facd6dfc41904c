"""Model operations of a zamba2 hybrid (the equations of the system's
``configs/zamba2_7b.py``), from a model's sizes (its configuration file's
entry). As in ``flops.py``: two operations per multiply-add of a matrix
product; norms, activations, softmax and the decay exponentials left out;
causal work counted as the lower triangle (``causal_half=False`` counts the
full square, as XLA's cost analysis of the unmasked programs does).

A shared block counts at every invocation: its weights are shared, its work
is not. The SSD's C.B^T is counted once per B/C group, as the model (and
the SSD kernel) computes it, and not once per head.
"""
from __future__ import annotations


def _padded_vocab(m) -> int:
    k = m["vocab_pad_multiple"]
    return ((m["vocab_size"] + k - 1) // k) * k


def _mamba_dims(m):
    d = m["d_model"]
    di = m["ssm_expand"] * d
    P, G, N, K = (m["ssm_head_dim"], m["ssm_groups"], m["ssm_state"],
                  m["ssm_conv"])
    return d, di, di // P, P, G, N, K


def _mamba_per_token(m) -> float:
    d, di, H, P, G, N, K = _mamba_dims(m)
    return (2 * d * (2 * di + 2 * G * N + H)        # z, x, B, C, dt
            + 2 * K * (di + 2 * G * N)              # convolutions
            + 2 * di * d)                           # output projection


def _shared_per_token(m) -> float:
    """One invocation's projections, per token: Q/K/V from 2 d, O, the
    gated MLP, its LoRA on gate/up and the linear after the block."""
    d, H, KV, hd = m["d_model"], m["num_heads"], m["num_kv_heads"], \
        m["head_dim"]
    ff, r = m["d_ff"], m["adapter_rank"]
    return (2 * 2 * d * (H + 2 * KV) * hd + 2 * H * hd * d
            + 2 * d * 2 * ff + 2 * ff * d
            + 2 * d * r + 2 * r * 2 * ff
            + 2 * d * d)


def _attention(m, seq: int, start: int, causal_half: bool) -> float:
    """Scores and their product with V, for ``seq`` new positions after
    ``start`` cached ones."""
    keys = seq * start + (seq * (seq + 1) / 2 if causal_half else seq * seq)
    return 4 * m["num_heads"] * m["head_dim"] * keys


def prefill(m, batch: int, seq: int, logits_per_row: int = None,
            causal_half: bool = True) -> float:
    """A full-sequence forward from an empty cache; ``logits_per_row``
    positions are unembedded (all of them unless given)."""
    d, di, H, P, G, N, K = _mamba_dims(m)
    L = min(m["ssm_chunk"], seq)
    chunks = -(-seq // L)
    pairs = L * (L + 1) / 2 if causal_half else L * L
    per_chunk = (2 * G * pairs * N                  # C.B^T, once a group
                 + 2 * H * pairs * P                # its product with x
                 + 2 * H * L * 2 * N * P)           # chunk states in/out
    mamba = seq * _mamba_per_token(m) + chunks * per_chunk
    shared = (seq * _shared_per_token(m)
              + _attention(m, seq, 0, causal_half))
    rows = seq if logits_per_row is None else logits_per_row
    return batch * (m["num_layers"] * mamba
                    + len(m["hybrid_layer_ids"]) * shared
                    + rows * 2 * d * _padded_vocab(m))


def decode_step(m, batch: int, start: int) -> float:
    """One token after ``start`` cached ones: the SSD's state update and
    read-out are 2 H P N operations each."""
    d, di, H, P, G, N, K = _mamba_dims(m)
    mamba = _mamba_per_token(m) + 4 * H * P * N
    shared = _shared_per_token(m) + _attention(m, 1, start, True)
    return batch * (m["num_layers"] * mamba
                    + len(m["hybrid_layer_ids"]) * shared
                    + 2 * d * _padded_vocab(m))


def generate(m, batch: int, prompt: int, new: int) -> float:
    """Prefill (last-position logits) and ``new - 1`` decode steps."""
    return prefill(m, batch, prompt, logits_per_row=1) + sum(
        decode_step(m, batch, prompt + k) for k in range(new - 1))
