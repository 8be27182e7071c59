"""Pallas TPU kernel for the Mamba2 SSD intra-chunk computation.

Grid: (batch, heads, num_chunks) — each program owns one (chunk x head)
tile and produces, entirely in VMEM:
    y_intra  (Q, P)  — the chunk-local quadratic ("attention-like") term
    state    (P, N)  — the chunk's contribution to the running SSM state
    decay_all (Q,)   — exp(cumsum(dt*A)) for the inter-chunk correction
    decay_chunk ()   — exp(full-chunk log-decay)
The O(S) inter-chunk recurrence (a tiny tensor contraction per chunk) stays
a lax.scan on the host side (ops.ssd) — it is bandwidth-trivial compared to
the intra-chunk quadratic term this kernel owns.

Tiling: Q (chunk length, default 128-256) x P (head dim 64/128) and (Q, N)
B/C tiles; all matmuls are (Q,N)x(N,Q), (Q,Q)x(Q,P), (N,Q)x(Q,P) — MXU
shapes.  Validated against ref.ssd_chunk_terms / ssd_reference in interpret
mode (tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, dtr_ref, dtc_ref, a_ref, b_ref, c_ref,
            y_ref, st_ref, dall_ref, dchunk_ref, *, Q):
    x = x_ref[0, 0, 0].astype(jnp.float32)       # (Q, P)
    dt_row = dtr_ref[0, 0, 0].astype(jnp.float32)  # (1, Q)
    dt_col = dtc_ref[0, 0, 0].astype(jnp.float32)  # (Q, 1)
    A = a_ref[0].astype(jnp.float32)             # (1, 1)
    Bc = b_ref[0, 0].astype(jnp.float32)         # (Q, N)
    Cc = c_ref[0, 0].astype(jnp.float32)         # (Q, N)

    # inclusive cumulative log-decay L, as a column (L_i) and a row (L_j):
    # masked reductions, since Mosaic has no cumsum
    iota_i = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    iota_j = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    causal = iota_j <= iota_i
    cum_col = jnp.sum(jnp.where(causal, dt_row * A, 0.0), axis=1,
                      keepdims=True)                            # (Q, 1)
    cum_row = jnp.sum(jnp.where(iota_i <= iota_j, dt_col * A, 0.0), axis=0,
                      keepdims=True)                            # (1, Q)
    total = jnp.sum(dt_row * A, axis=1, keepdims=True)          # (1, 1)
    L = jnp.where(causal, jnp.exp(cum_col - cum_row), 0.0)      # (Qi, Qj)
    cb = jax.lax.dot_general(Cc, Bc, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Qi,Qj)
    M = cb * L * dt_row
    y = jax.lax.dot_general(M, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (Q,P)
    wB = Bc * (jnp.exp(total - cum_col) * dt_col)                 # (Q,N)
    state = jax.lax.dot_general(x, wB, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (P,N)
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)
    st_ref[0, 0, 0] = state.astype(st_ref.dtype)
    dall_ref[0, 0, 0] = jnp.exp(cum_row).astype(dall_ref.dtype)
    dchunk_ref[0, 0, 0] = jnp.exp(total).astype(dchunk_ref.dtype)


def ssd_chunk_kernel(x, dt, A, B_, C_, *, chunk: int, interpret: bool):
    """Intra-chunk terms for all chunks.

    x: (B,S,H,P); dt: (B,S,H) f32; A: (H,); B_/C_: (B,S,N).
    Returns y_intra (B,S,H,P) f32, states (B,H,nc,P,N) f32,
    decay_all (B,H,nc,Q) f32, decay_chunk (B,H,nc) f32.

    Every block's last two dims are either the array's own or (8, 128)
    multiples, as the TPU requires: per-chunk vectors travel as (1, Q) rows
    or (Q, 1) columns, and per-head scalars as (1, 1) tiles.
    """
    Bsz, S, H, P = x.shape
    N = B_.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0, (S, Q)
    nc = S // Q
    # layouts: (B,H,nc,Q,*) for per-(head,chunk) tiles
    xr = x.reshape(Bsz, nc, Q, H, P).transpose(0, 3, 1, 2, 4)
    dtr = dt.reshape(Bsz, nc, Q, H).transpose(0, 3, 1, 2)
    Br = B_.reshape(Bsz, nc, Q, N)
    Cr = C_.reshape(Bsz, nc, Q, N)
    tile = lambda b, h, c: (b, h, c, 0, 0)  # noqa: E731

    y, st, dall, dchunk = pl.pallas_call(
        functools.partial(_kernel, Q=Q),
        grid=(Bsz, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), tile),
            pl.BlockSpec((1, 1, 1, 1, Q), tile),
            pl.BlockSpec((1, 1, 1, Q, 1), tile),
            pl.BlockSpec((1, 1, 1), lambda b, h, c: (h, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, h, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, h, c: (b, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), tile),
            pl.BlockSpec((1, 1, 1, P, N), tile),
            pl.BlockSpec((1, 1, 1, 1, Q), tile),
            pl.BlockSpec((1, 1, 1, 1, 1), tile),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, H, nc, Q, P), jnp.float32),
            jax.ShapeDtypeStruct((Bsz, H, nc, P, N), jnp.float32),
            jax.ShapeDtypeStruct((Bsz, H, nc, 1, Q), jnp.float32),
            jax.ShapeDtypeStruct((Bsz, H, nc, 1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(xr, dtr[:, :, :, None, :], dtr[..., None], A.reshape(H, 1, 1), Br, Cr)
    # y back to (B,S,H,P)
    y = y.transpose(0, 2, 3, 1, 4).reshape(Bsz, S, H, P)
    return y, st, dall[:, :, :, 0], dchunk[:, :, :, 0, 0]
