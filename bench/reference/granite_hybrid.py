"""Plain float32 reference of a Granite-4.0-H hybrid stack (granitemoehybrid).

Written from the architecture's description (the Hugging Face
``granitemoehybrid`` configuration and its layer equations), not from the
program's code.  The token embeddings are multiplied by
``embedding_multiplier``.  Each layer: pre-RMSNorm, its mixer (the one
its weights are of: ``layer_types`` in the configuration), the branch
times ``residual_multiplier`` added to the residual; pre-RMSNorm, the routed experts' part plus the shared
expert, times ``residual_multiplier``, added to the residual.  A final
RMSNorm, then logits against the tied embedding, divided by
``logits_scaling``.

- Mamba2 mixer: as in ``mamba2.py`` (the scan in its quadratic form,
  ``ssd`` from there), with a bias on the depthwise convolution and the
  gated RMSNorm, norm(y * SiLU(z)) over the whole inner width (one group),
  before the output projection.
- Attention: grouped-query causal softmax attention with no positional
  encoding (NoPE); scores are q.k times ``attention_multiplier``.
- Experts: a router over all ``router_experts`` experts; the top
  ``num_experts_per_tok`` logits of a token, softmax over those; SiLU-gated
  experts of width ``intermediate_size``; a SiLU-gated shared expert of
  width ``shared_intermediate_size`` on every token.

Departures from the published model (``bench/configs/granite-4.0-h-small
.json`` states each): only ``num_local_experts`` of the router's experts
are held, from ``first_expert`` on, and the absent experts' part of each
expert layer is left out, as one chip of an expert-parallel deployment
leaves it to the others (the program does the same); 10 of the 40 layers,
one whole period; the head width (128 = hidden / heads) and the expert
width (``intermediate_size``) are read, not given; random weights.

Weights arrive as a flat dict keyed by storage path (``bench.weights
.flat``): ``layers/<j>/...`` holds position j of the layer period, stacked
over the periods on axis 0, so layer i is position i % period of period
i // period.  An attention layer has ``mixer/wq``, a Mamba2 layer
``mixer/in_proj``.  RMSNorm scales are stored as their offset from 1.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .mamba2 import ssd
from .numerics import mm
from .transformer import NEG, rms_norm


def layers(weights) -> list:
    """Each layer's weights, in order, keyed without ``layers/<j>/``."""
    period = len({k.split("/")[1] for k in weights if k.startswith("layers/")})
    out = []
    for i in range(period * weights["layers/0/norm1"].shape[0]):
        pre = f"layers/{i % period}/"
        out.append({k[len(pre):]: v[i // period] for k, v in weights.items()
                    if k.startswith(pre)})
    return out


def mamba_mixer(h, w, cfg, prec):
    b, S, _ = h.shape
    H, P, N = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    inner = H * P
    zxbcdt = mm("bsd,de->bse", h, w["mixer/in_proj"], prec)
    z = zxbcdt[..., :inner]
    xbc = zxbcdt[..., inner:2 * inner + 2 * N]
    dt = zxbcdt[..., 2 * inner + 2 * N:]
    conv_w = w["mixer/conv_w"]                                   # (W, C)
    W = conv_w.shape[0]
    xp = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(xp[:, i:i + S] * conv_w[i] for i in range(W))
                      + w["mixer/conv_b"])
    xs = xbc[..., :inner].reshape(b, S, H, P)
    Bm, Cm = xbc[..., inner:inner + N], xbc[..., inner + N:]
    dt = jax.nn.softplus(dt + w["mixer/dt_bias"])
    A = -jnp.exp(w["mixer/A_log"])
    y = ssd(xs, dt, A, Bm, Cm, prec) + xs * w["mixer/D"][None, None, :, None]
    y = y.reshape(b, S, inner) * jax.nn.silu(z)
    y = rms_norm(y, w["mixer/norm"], cfg["rms_norm_eps"])
    return mm("bse,ed->bsd", y, w["mixer/out_proj"], prec)


def attention(h, w, cfg, prec):
    S = h.shape[1]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    q = mm("bsd,dhk->bshk", h, w["mixer/wq"], prec)
    k = mm("bsd,dhk->bshk", h, w["mixer/wk"], prec)
    v = mm("bsd,dhk->bshk", h, w["mixer/wv"], prec)
    # query head j reads key/value head j // (H // Hkv)
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    s = mm("bqhd,bkhd->bhqk", q, k, prec) * cfg["attention_multiplier"]
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, s, NEG), axis=-1)
    o = mm("bhqk,bkhd->bqhd", p, v, prec)
    return mm("bshk,hkd->bsd", o, w["mixer/wo"], prec)


def gated_mlp(h, wi, wg, wo, prec):
    up = mm("bsd,df->bsf", h, wi, prec)
    gate = mm("bsd,df->bsf", h, wg, prec)
    return mm("bsf,fd->bsd", jax.nn.silu(gate) * up, wo, prec)


def experts(h, w, cfg, prec):
    """The held experts' part of the routed output: for each held expert
    e, its gate for each token (0 where the token did not pick it) times
    its output."""
    logits = mm("bsd,de->bse", h, w["ffn/router"], prec)
    top, idx = jax.lax.top_k(logits, cfg["num_experts_per_tok"])
    gates = jax.nn.softmax(top, axis=-1)

    def one(acc, e):
        wi, wg, wo, expert = e
        gate = jnp.sum(jnp.where(idx == expert, gates, 0.0), axis=-1)
        return acc + gate[..., None] * gated_mlp(h, wi, wg, wo, prec), None

    held = w["ffn/wi"].shape[0]
    ids = cfg["first_expert"] + jnp.arange(held)
    out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (w["ffn/wi"], w["ffn/wg"], w["ffn/wo"], ids))
    return out


def hidden(weights, tokens, cfg, prec="f32"):
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    x = jnp.take(weights["embed"], tokens, axis=0) * cfg["embedding_multiplier"]
    for w in layers(weights):
        h = rms_norm(x, w["norm1"], eps)
        mix = attention if "mixer/wq" in w else mamba_mixer
        x = x + r * mix(h, w, cfg, prec)
        h = rms_norm(x, w["norm2"], eps)
        shared = gated_mlp(h, w["shared/wi"], w["shared/wg"], w["shared/wo"],
                           prec)
        x = x + r * (experts(h, w, cfg, prec) + shared)
    return rms_norm(x, weights["final_norm"], eps)


def token_nll(weights, tokens, targets, cfg, prec="f32"):
    """(B, S) negative log-likelihood of each target token."""
    weights = {k: v.astype(jnp.float32) for k, v in weights.items()}
    h = hidden(weights, tokens, cfg, prec)
    logits = mm("bsd,vd->bsv", h, weights["embed"], prec) / cfg["logits_scaling"]
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jax.nn.logsumexp(logits, axis=-1) - gold


def flops_per_token(s: dict, seq: int) -> float:
    """Forward FLOPs a token at context ``seq`` (``bench/flops.py``).  The
    held experts are counted at the rows a token sends them on average,
    k x held / router_experts expert evaluations (an expectation: the
    rows a run routes here vary with its tokens)."""
    d, V = s["hidden_size"], s["vocab_size"]
    H, P, N = s["mamba_n_heads"], s["mamba_d_head"], s["mamba_d_state"]
    E, Q, W = H * P, s["mamba_chunk_size"], s["mamba_d_conv"]
    q = min(Q, seq)
    mamba = (d * (2 * E + 2 * N + H) + E * d + W * (E + 2 * N)
             + N * (q + 1) / 2 + H * P * (q + 1) / 2 + 2 * H * P * N)
    Hq, Hkv, hd = (s["num_attention_heads"], s["num_key_value_heads"],
                   s["head_dim"])
    attn = d * Hq * hd * 2 + d * Hkv * hd * 2 + 2 * Hq * hd * (seq + 1) / 2
    rows = s["num_experts_per_tok"] * s["num_local_experts"] / s["router_experts"]
    ffn = (d * s["router_experts"] + rows * 3 * d * s["intermediate_size"]
           + 3 * d * s["shared_intermediate_size"])
    mixers = sum(attn if t == "attention" else mamba for t in s["layer_types"])
    return 2.0 * (mixers + len(s["layer_types"]) * ffn + d * V)
