"""Model FLOPs per token, from a configuration file's sizes.

Counts the multiply-adds the architecture requires, at 2 FLOPs each: every
matrix product with a parameter matrix (the tied output projection included,
the embedding lookup not), and the products between activations (attention's
scores and weighted values, over the causal half of the context; the SSD's
intra-chunk and state products).  Training is three times the forward (the
backward pass is twice it); recomputation under remat is not counted.
"""
from __future__ import annotations


def _transformer(s: dict, seq: int) -> float:
    d, L = s["hidden_size"], s["num_hidden_layers"]
    H, Hkv, hd = (s["num_attention_heads"], s["num_key_value_heads"],
                  s["head_dim"])
    per_layer = d * H * hd * 2 + d * Hkv * hd * 2 + 3 * d * s["intermediate_size"]
    attn = 2 * H * hd * (seq + 1) / 2          # q.k and p.v, causal half
    return 2.0 * (L * (per_layer + attn) + d * s["vocab_size"])


def _mamba2(s: dict, seq: int) -> float:
    d, L, E, N = (s["hidden_size"], s["num_hidden_layers"], s["d_inner"],
                  s["state_size"])
    P, Q, W = s["head_dim"], s["chunk_size"], s["conv_kernel"]
    H = E // P
    proj = d * (2 * E + 2 * N + H) + E * d + W * (E + 2 * N)
    q = min(Q, seq)
    ssd = N * (q + 1) / 2 + H * P * (q + 1) / 2 + 2 * H * P * N
    return 2.0 * (L * (proj + ssd) + d * s["vocab_size"])


def per_token(conf: dict, seq: int, training: bool) -> float:
    fwd = {"transformer": _transformer, "mamba2": _mamba2}[
        conf["reference"]](conf["sizes"], seq)
    return 3.0 * fwd if training else fwd
