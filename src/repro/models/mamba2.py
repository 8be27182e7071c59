"""Mamba2 (SSD — state-space duality) mixer: chunked train scan + O(1) decode.

Train/prefill uses the chunked SSD algorithm (arXiv:2405.21060 §6): the
sequence is split into chunks of length Q; within a chunk the contribution is
a (masked, decay-weighted) attention-like quadratic term; across chunks a
recurrence over per-chunk states (B,H,P,N) carries history.  This is the
pure-JAX oracle; ``repro.kernels.ssd`` provides the Pallas TPU kernel for the
intra-chunk term.

Decode keeps the SSM state (B,H,P,N) + a rolling conv window; each step is
O(1) in context length — this is what makes the 500k-context cells runnable.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import shard_map


def mamba_params_spec(cfg):
    d, inner, nh, N = cfg.d_model, cfg.inner_dim, cfg.ssm_heads, cfg.ssm_state
    cw = cfg.conv_width
    return {
        "in_proj": ((d, 2 * inner + 2 * N + nh), ("embed_w", "ssm_inner")),
        "out_proj": ((inner, d), ("ssm_inner", "embed_w")),
        "conv_w": ((cw, inner + 2 * N), (None, "ssm_inner")),
        "A_log": ((nh,), ("ssm_heads",)),
        "D": ((nh,), ("ssm_heads",)),
        "dt_bias": ((nh,), ("ssm_heads",)),
    }


class MambaCache(NamedTuple):
    h: jnp.ndarray         # (B, H, P, N) ssm state
    conv: jnp.ndarray      # (B, conv_width-1, inner + 2N) rolling conv input


def _split_proj(cfg, zxbcdt):
    inner, N, nh = cfg.inner_dim, cfg.ssm_state, cfg.ssm_heads
    z, xBC, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * N], axis=-1)
    return z, xBC, dt


def _causal_conv(xBC, conv_w, prev: Optional[jnp.ndarray] = None):
    """Depthwise causal conv1d. xBC: (B, S, C); conv_w: (W, C)."""
    W = conv_w.shape[0]
    if prev is None:
        prev = jnp.zeros((xBC.shape[0], W - 1, xBC.shape[2]), xBC.dtype)
    xp = jnp.concatenate([prev, xBC], axis=1)
    out = sum(xp[:, i:i + xBC.shape[1], :] * conv_w[i][None, None, :]
              for i in range(W))
    return jax.nn.silu(out), xp[:, -(W - 1):, :]


def ssd_chunked(x, dt, A, B_, C_, chunk: int, use_pallas: bool = False,
                h0: Optional[jnp.ndarray] = None):
    """Chunked SSD scan.

    x: (B, S, H, P); dt: (B, S, H) (post-softplus); A: (H,) (negative);
    B_, C_: (B, S, N).  Returns y: (B, S, H, P), final state (B, H, P, N).
    """
    if use_pallas:
        from repro.kernels import ops as kops
        return kops.ssd(x, dt, A, B_, C_, chunk=chunk, h0=h0)
    from repro.kernels.ref import ssd_reference
    return ssd_reference(x, dt, A, B_, C_, chunk=chunk, h0=h0)


def sharded_ssd(mesh, x, dt, A, B_, C_, chunk: int, use_pallas: bool = False,
                rules=None):
    """shard_map'd SSD: batch per the partition rules, heads on model;
    fully local (the SSD recurrence has no cross-batch/head coupling)."""
    from jax.sharding import PartitionSpec as P

    from repro.sharding.partition import PartitionRules
    rules = rules or PartitionRules()
    B, S, H, _ = x.shape
    bres = tuple(rules.spec_for(("batch",), (B,), mesh))
    bspec = bres[0] if bres else None
    b_axes = (tuple(bspec) if isinstance(bspec, tuple)
              else ((bspec,) if bspec else ()))
    M = 1 if "model" in b_axes else mesh.shape.get("model", 1)
    hspec = "model" if (M > 1 and H % M == 0) else None
    if bspec is None and hspec is None:
        return ssd_chunked(x, dt, A, B_, C_, chunk, use_pallas)
    fn = shard_map(
        lambda x_, dt_, A_, b_, c_: ssd_chunked(x_, dt_, A_, b_, c_, chunk,
                                                use_pallas),
        mesh=mesh,
        in_specs=(P(bspec, None, hspec, None), P(bspec, None, hspec),
                  P(hspec), P(bspec, None, None), P(bspec, None, None)),
        out_specs=(P(bspec, None, hspec, None), P(bspec, hspec, None, None)),
        check_vma=False)
    return fn(x, dt, A, B_, C_)


def mamba_layer(cfg, w, x, *, sctx, cache: Optional[MambaCache] = None,
                use_pallas: bool = False):
    """Pre-norm Mamba2 mixer. x: (B, S, D). Returns (out, new_cache)."""
    B, S, D = x.shape
    inner, N, nh, P = cfg.inner_dim, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    zxbcdt = jnp.einsum("bsd,de->bse", x, w["in_proj"])
    z, xBC, dt = _split_proj(cfg, zxbcdt)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + w["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(w["A_log"].astype(jnp.float32))             # (H,)

    if cache is None:
        xBC, conv_tail = _causal_conv(xBC, w["conv_w"])
        xs, B_, C_ = jnp.split(xBC, [inner, inner + N], axis=-1)
        xh = xs.reshape(B, S, nh, P)
        xh = sctx.act(xh, ("batch", "seq", "ssm_heads", None))
        if sctx.mesh is not None:
            y, hT = sharded_ssd(sctx.mesh, xh, dt, A, B_, C_, cfg.ssm_chunk,
                                use_pallas, rules=sctx.rules)
        else:
            y, hT = ssd_chunked(xh, dt, A, B_, C_, cfg.ssm_chunk, use_pallas)
        y = y + xh * w["D"].astype(y.dtype)[None, None, :, None]
        new_cache = MambaCache(hT.astype(jnp.float32), conv_tail)
    else:
        # single-token recurrence: h <- exp(dt*A) h + dt * (B outer x)
        xBC, conv_tail = _causal_conv(xBC, w["conv_w"], prev=cache.conv)
        xs, B_, C_ = jnp.split(xBC, [inner, inner + N], axis=-1)
        xh = xs.reshape(B, 1, nh, P)[:, 0]                    # (B, H, P)
        dt1 = dt[:, 0]                                        # (B, H)
        decay = jnp.exp(dt1 * A[None, :])                     # (B, H)
        dBx = jnp.einsum("bh,bn,bhp->bhpn", dt1, B_[:, 0].astype(jnp.float32),
                         xh.astype(jnp.float32))
        h = cache.h * decay[..., None, None] + dBx            # (B, H, P, N)
        y = jnp.einsum("bhpn,bn->bhp", h, C_[:, 0].astype(jnp.float32))
        y = y[:, None].astype(x.dtype)                        # (B, 1, H, P)
        y = y + xh[:, None] * w["D"].astype(y.dtype)[None, None, :, None]
        new_cache = MambaCache(h, conv_tail)

    y = y.reshape(B, S, inner)
    y = y * jax.nn.silu(z)
    out = jnp.einsum("bse,ed->bsd", y, w["out_proj"])
    return sctx.act(out, ("batch", "seq", None)), new_cache
