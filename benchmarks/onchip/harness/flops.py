"""Model operations per call, from a model's sizes (its configuration file's
entry). Matrix products count two operations per multiply-add; norms,
activations and softmax are left out.

Causal work counts the lower triangle only: attention at position t attends
to t + 1 keys, and the state-space dual form within a chunk of L tokens
multiplies L (L + 1) / 2 pairs. ``causal_half=False`` counts the full square
instead, as XLA's cost analysis of the unmasked form does. Training counts
three forward passes; recomputation is not counted.
"""
from __future__ import annotations


def _padded_vocab(m) -> int:
    k = m["vocab_pad_multiple"]
    return ((m["vocab_size"] + k - 1) // k) * k


def mamba2_forward(m, batch: int, seq: int, logits_per_row: int = None,
                   causal_half: bool = True) -> float:
    """A full-sequence forward; ``logits_per_row`` positions are unembedded
    (all of them unless given)."""
    d = m["d_model"]
    di = m["ssm_expand"] * d
    P, G, N, K = m["ssm_head_dim"], m["ssm_groups"], m["ssm_state"], \
        m["ssm_conv"]
    H = di // P
    L = min(m["ssm_chunk"], seq)
    chunks = -(-seq // L)
    pairs = L * (L + 1) / 2 if causal_half else L * L
    per_token = (2 * d * (2 * di + 2 * G * N + H)       # z, x, B, C, dt
                 + 2 * K * (di + 2 * G * N)            # convolutions
                 + 2 * di * d)                         # output projection
    per_chunk = 2 * H * pairs * (N + P)                # C.B and its product
    per_chunk += 2 * H * L * 2 * N * P                 # chunk states in/out
    layer = seq * per_token + chunks * per_chunk
    rows = seq if logits_per_row is None else logits_per_row
    return batch * (m["num_layers"] * layer + rows * 2 * d * _padded_vocab(m))


def dense_forward(m, batch: int, seq: int, start: int = 0,
                  logits_per_row: int = None, causal_half: bool = True
                  ) -> float:
    """``seq`` new positions after ``start`` cached ones, causal."""
    d, H, KV, hd, ff = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                        m["head_dim"], m["d_ff"])
    per_token = (2 * d * (H + 2 * KV) * hd + 2 * H * hd * d
                 + 2 * 3 * d * ff)
    # keys attended, summed over the new positions: (start+1) .. (start+seq)
    keys = seq * start + (seq * (seq + 1) / 2 if causal_half else seq * seq)
    layer = seq * per_token + 4 * H * hd * keys
    rows = seq if logits_per_row is None else logits_per_row
    return batch * (m["num_layers"] * layer + rows * 2 * d * _padded_vocab(m))


def generate(m, batch: int, prompt: int, new: int) -> float:
    """Prefill (last-position logits) and ``new - 1`` decode steps."""
    total = dense_forward(m, batch, prompt, logits_per_row=1)
    for k in range(new - 1):
        total += dense_forward(m, batch, 1, start=prompt + k)
    return total


def train(m, batch: int, seq: int, steps: int, family: str) -> float:
    fwd = (mamba2_forward if family == "ssm" else dense_forward)(m, batch, seq)
    return 3.0 * fwd * steps
