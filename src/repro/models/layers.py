"""Shared layer primitives: RMSNorm, RoPE, MLP, softcap, embeddings."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x, weight, eps: float = 1e-6):
    """RMSNorm with f32 *accumulation* but no full-tensor f32 materialization
    (the reduction accumulates in f32 via ``dtype=``; keeping x in bf16
    halves layer-boundary checkpoint traffic — see transformer.group_body)."""
    dt = x.dtype
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True,
                   dtype=jnp.float32)
    scale = (jax.lax.rsqrt(var + eps)
             * (1.0 + weight.astype(jnp.float32))).astype(dt)
    return x * scale


def softcap(x, cap: float):
    """Gemma2-style logit soft capping."""
    if not cap:
        return x
    return cap * jnp.tanh(x / cap)


# ------------------------------ RoPE ---------------------------------- #
# Interleaved (even/odd pair) rotary embedding: pairs are adjacent in the
# head_dim axis, so sharding head_dim into even-sized chunks never splits a
# rotation pair (required when TP falls back to head_dim sharding).

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                          # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    cos = jnp.cos(angles)[..., None, :]                   # (..., S, 1, D/2)
    sin = jnp.sin(angles)[..., None, :]
    x1 = x[..., 0::2].astype(jnp.float32)
    x2 = x[..., 1::2].astype(jnp.float32)
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    out = jnp.stack([o1, o2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


# ------------------------------ MLP ----------------------------------- #

@jax.named_scope("mlp")
def mlp(x, w, gated: bool):
    """w: {'wi': (D,F), 'wg': (D,F) if gated, 'wo': (F,D)}."""
    h = jnp.einsum("...d,df->...f", x, w["wi"])
    if gated:
        g = jnp.einsum("...d,df->...f", x, w["wg"])
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h)
    return jnp.einsum("...f,fd->...d", h, w["wo"])
