"""Plain float32 reference of a Mamba2 (SSD) stack.

Each layer: pre-RMSNorm, the Mamba2 mixer, residual.  The mixer projects to
(z, x, B, C, dt); a depthwise causal convolution and SiLU act on (x, B, C);
dt = softplus(dt + dt_bias), A = -exp(A_log); the state-space scan

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,    y_t = h_t C_t + D x_t

is computed in its quadratic ("attention") form over the whole sequence,
    y_t = sum_{s<=t} (C_t . B_s) exp(sum_{s<r<=t} dt_r A) dt_s x_s,
not chunked; then y * SiLU(z) and the output projection.  One group of B and
C is shared by all heads (ngroups = 1).  As the configuration states, the
block has no gated RMSNorm before the output projection and no convolution
bias (see ``bench/configs/mamba2-1.3b.json``).

Weights arrive as a flat dict keyed by storage path, per-layer arrays stacked
on axis 0, RMSNorm scales stored as their offset from 1.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .numerics import mm
from .transformer import rms_norm, split_layers

HEAD_GROUP = 16          # heads whose (S, S) decay matrices are live at once


def ssd(x, dt, A, Bm, Cm, prec):
    """x: (b, S, H, P); dt: (b, S, H); A: (H,); Bm, Cm: (b, S, N)."""
    b, S, H, P = x.shape
    cb = mm("btn,bsn->bts", Cm, Bm, prec)                      # (b, S, S)
    cum = jnp.cumsum(dt * A[None, None, :], axis=1)            # (b, S, H)
    causal = jnp.tril(jnp.ones((S, S), bool))[None, :, :, None]
    g = min(HEAD_GROUP, H)

    def group(args):
        xg, dtg, cumg = args                    # (b,S,g,P), (b,S,g), (b,S,g)
        seg = cumg[:, :, None, :] - cumg[:, None, :, :]        # (b,T,S,g)
        decay = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
        return mm("btsh,bshp->bthp", cb[..., None] * decay,
                  dtg[..., None] * xg, prec)

    split = lambda a: jnp.moveaxis(                            # noqa: E731
        a.reshape(a.shape[:2] + (H // g, g) + a.shape[3:]), 2, 0)
    ys = jax.lax.map(group, (split(x), split(dt), split(cum)))
    return jnp.moveaxis(ys, 0, 2).reshape(b, S, H, P)


def mixer(h, w, cfg, prec):
    b, S, _ = h.shape
    inner, N = cfg["d_inner"], cfg["state_size"]
    P = cfg["head_dim"]
    H = inner // P
    zxbcdt = mm("bsd,de->bse", h, w["mixer/in_proj"], prec)
    z = zxbcdt[..., :inner]
    xbc = zxbcdt[..., inner:2 * inner + 2 * N]
    dt = zxbcdt[..., 2 * inner + 2 * N:]
    conv_w = w["mixer/conv_w"].astype(jnp.float32)              # (W, C)
    W = conv_w.shape[0]
    xp = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(xp[:, i:i + S] * conv_w[i] for i in range(W)))
    xs = xbc[..., :inner].reshape(b, S, H, P)
    Bm, Cm = xbc[..., inner:inner + N], xbc[..., inner + N:]
    dt = jax.nn.softplus(dt + w["mixer/dt_bias"])
    A = -jnp.exp(w["mixer/A_log"].astype(jnp.float32))
    y = ssd(xs, dt, A, Bm, Cm, prec) + xs * w["mixer/D"][None, None, :, None]
    y = y.reshape(b, S, inner) * jax.nn.silu(z)
    return mm("bse,ed->bsd", y, w["mixer/out_proj"], prec)


def hidden(weights, tokens, cfg, prec="f32"):
    stacked, top = split_layers(weights)
    eps = cfg["rms_norm_eps"]
    x = jnp.take(top["embed"], tokens, axis=0)

    def body(c, w):
        return c + mixer(rms_norm(c, w["norm1"], eps), w, cfg, prec), None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, stacked)
    return rms_norm(x, top["final_norm"], eps)


def token_nll(weights, tokens, targets, cfg, prec="f32"):
    """(B, S) negative log-likelihood of each target token."""
    weights = {k: v.astype(jnp.float32) for k, v in weights.items()}
    h = hidden(weights, tokens, cfg, prec)
    logits = mm("bsd,vd->bsv", h, weights["embed"], prec)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jax.nn.logsumexp(logits, axis=-1) - gold


def flops_per_token(s: dict, seq: int) -> float:
    """Forward FLOPs a token at context ``seq`` (``bench/flops.py``)."""
    d, L, E, N = (s["hidden_size"], s["num_hidden_layers"], s["d_inner"],
                  s["state_size"])
    P, Q, W = s["head_dim"], s["chunk_size"], s["conv_kernel"]
    H = E // P
    proj = d * (2 * E + 2 * N + H) + E * d + W * (E + 2 * N)
    q = min(Q, seq)
    ssd = N * (q + 1) / 2 + H * P * (q + 1) / 2 + 2 * H * P * N
    return 2.0 * (L * (proj + ssd) + d * s["vocab_size"])
