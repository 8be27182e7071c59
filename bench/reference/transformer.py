"""Plain float32 reference of a dense GQA decoder (Llama family: SmolLM).

Written from the architecture's description, not from the program's code.
Each layer: pre-RMSNorm, grouped-query causal softmax attention with rotary
positions, residual; pre-RMSNorm, SiLU-gated MLP, residual.  A final RMSNorm,
then logits against the tied embedding.  The loss is the mean token
cross-entropy.

Weights arrive as a flat dict keyed by their storage path (see
``bench.weights.flat``); per-layer arrays are stacked on axis 0.  Two storage
conventions of the configuration are followed: an RMSNorm scale is stored as
its offset from 1, and rotary positions rotate adjacent pairs of a head's
dimensions (2i, 2i+1), a fixed permutation of the head dimension relative to
the half-split layout of the Hugging Face checkpoint.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .numerics import mm

NEG = -1e30


def rms_norm(x, offset, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + offset)


def rope(x, theta):
    """x: (B, S, H, D); rotates pairs (2i, 2i+1) by pos * theta^(-2i/D)."""
    S, D = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def attention(h, w, cfg, prec):
    B, S, _ = h.shape
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    q = rope(mm("bsd,dhk->bshk", h, w["mixer/wq"], prec), cfg["rope_theta"])
    k = rope(mm("bsd,dhk->bshk", h, w["mixer/wk"], prec), cfg["rope_theta"])
    v = mm("bsd,dhk->bshk", h, w["mixer/wv"], prec)
    # query head j reads key/value head j // (H // Hkv)
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    s = mm("bqhd,bkhd->bhqk", q, k, prec) / jnp.sqrt(float(q.shape[-1]))
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, s, NEG), axis=-1)
    o = mm("bhqk,bkhd->bqhd", p, v, prec)
    return mm("bshk,hkd->bsd", o, w["mixer/wo"], prec)


def mlp(h, w, prec):
    up = mm("bsd,df->bsf", h, w["ffn/wi"], prec)
    gate = mm("bsd,df->bsf", h, w["ffn/wg"], prec)
    return mm("bsf,fd->bsd", jax.nn.silu(gate) * up, w["ffn/wo"], prec)


def layer(x, w, cfg, prec):
    eps = cfg["rms_norm_eps"]
    x = x + attention(rms_norm(x, w["norm1"], eps), w, cfg, prec)
    return x + mlp(rms_norm(x, w["norm2"], eps), w, prec)


def split_layers(weights):
    """(stacked per-layer weights keyed without the layer prefix, rest)."""
    pre = "layers/0/"
    stacked = {k[len(pre):]: v for k, v in weights.items() if k.startswith(pre)}
    return stacked, {k: v for k, v in weights.items() if not k.startswith(pre)}


def hidden(weights, tokens, cfg, prec="f32"):
    stacked, top = split_layers(weights)
    x = jnp.take(top["embed"], tokens, axis=0)
    body = jax.checkpoint(lambda c, w: (layer(c, w, cfg, prec), None))
    x, _ = jax.lax.scan(body, x, stacked)
    return rms_norm(x, top["final_norm"], cfg["rms_norm_eps"])


def token_nll(weights, tokens, targets, cfg, prec="f32"):
    """(B, S) negative log-likelihood of each target token."""
    weights = {k: v.astype(jnp.float32) for k, v in weights.items()}
    h = hidden(weights, tokens, cfg, prec)
    logits = mm("bsd,vd->bsv", h, weights["embed"], prec)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jax.nn.logsumexp(logits, axis=-1) - gold


def flops_per_token(s: dict, seq: int) -> float:
    """Forward FLOPs a token at context ``seq`` (``bench/flops.py``)."""
    d, L = s["hidden_size"], s["num_hidden_layers"]
    H, Hkv, hd = (s["num_attention_heads"], s["num_key_value_heads"],
                  s["head_dim"])
    per_layer = d * H * hd * 2 + d * Hkv * hd * 2 + 3 * d * s["intermediate_size"]
    attn = 2 * H * hd * (seq + 1) / 2          # q.k and p.v, causal half
    return 2.0 * (L * (per_layer + attn) + d * s["vocab_size"])
