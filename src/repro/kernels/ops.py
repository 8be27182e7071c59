"""jit'd public wrappers for the Pallas kernels.

``interpret=None`` auto-selects: compiled on TPU, interpret elsewhere — so
the same model code runs the real kernel on hardware and the
Python-executed kernel body on the CPU container.

``splash_attention`` wraps the fused flash-attention kernel that ships with
JAX (``jax.experimental.pallas.ops.tpu.splash_attention``): forward, ``dq``
and ``dkv`` kernels behind a custom VJP, grouped query heads indexed in the
kernel, blocks the mask removes entirely skipped.  The models reach it
through ``repro.models.attention.flash_attention``.

The SSD wrapper composes the Pallas intra-chunk kernel with the host-side
inter-chunk recurrence (a lax.scan over per-chunk states) and defines a
custom VJP that recomputes kernel terms in the backward pass via the jnp
reference (training path memory: O(S) states, no stored (Q,Q) matrices).
"""
from __future__ import annotations

from functools import lru_cache, partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as _splash, splash_attention_mask as _masks)

from . import flash_attention as _fa
from . import ssd as _ssd
from . import ref as _ref

LANES = 128


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is None:
        return not on_tpu()
    return interpret


def _splash_blocks(S: int) -> tuple:
    """(padded length, ``BlockSizes``) for a sequence of ``S``: one block of
    up to 512 rows and columns, in whole lanes, for the forward, the ``dq``
    and the ``dkv`` kernel alike."""
    block = min(-(-S // LANES) * LANES, 512)
    return -(-S // block) * block, _splash.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        block_q_dq=block, block_kv_dq=block)


@lru_cache(maxsize=None)
def _splash_kernel(S: int, Hq: int, window: int, attn_softcap: float,
                   interpret: bool):
    """The kernel for one padded length, head count and mask; its block
    tables are built from the mask once, on the host."""
    _, blocks = _splash_blocks(S)
    mask = (_masks.LocalMask((S, S), (window - 1, 0), 0) if window
            else _masks.CausalMask((S, S)))
    with jax.ensure_compile_time_eval():
        return _splash.make_splash_mha(
            _masks.MultiHeadMask((mask,) * Hq), block_sizes=blocks,
            head_shards=1, q_seq_shards=1,
            attn_logits_soft_cap=attn_softcap or None, interpret=interpret)


def splash_attention(q, k, v, *, window: int = 0, attn_softcap: float = 0.0,
                     interpret: Optional[bool] = None):
    """Causal GQA flash attention, fused: q (B,S,Hq,D) x k/v (B,S,Hkv,D).

    Same contract as ``repro.kernels.ref.attention_reference`` with
    ``causal=True``; ``window`` keeps keys with q - window < kv <= q.  S is
    padded up to the block multiple: under the causal mask padded keys come
    after every real query, and padded query rows are dropped.  The scale
    1/sqrt(D) is applied to q (exact for a power-of-two D).  Softmax
    statistics and accumulators are f32; p meets V in V's dtype.
    """
    B, S, Hq, D = q.shape
    S_pad, _ = _splash_blocks(S)
    kernel = _splash_kernel(S_pad, Hq, window, float(attn_softcap),
                            _auto_interpret(interpret))

    def heads_major(x):                       # (B,S,H,D) -> (B,H,S_pad,D)
        if S_pad != S:
            x = jnp.pad(x, ((0, 0), (0, S_pad - S), (0, 0), (0, 0)))
        return x.transpose(0, 2, 1, 3)

    q = q * jnp.asarray(D ** -0.5, q.dtype)
    out = jax.vmap(kernel)(heads_major(q), heads_major(k), heads_major(v))
    return out.transpose(0, 2, 1, 3)[:, :S]


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    attn_softcap: float = 0.0, block_q: int = 128,
                    block_k: int = 128, interpret: Optional[bool] = None):
    """Forward-only Pallas flash attention (``use_pallas=True``; no entry
    point passes it).  The models' path is ``splash_attention``."""
    return _fa.flash_attention_fwd(
        q, k, v, causal=causal, window=window, attn_softcap=attn_softcap,
        block_q=block_q, block_k=block_k,
        interpret=_auto_interpret(interpret))


@partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def ssd(x, dt, A, B_, C_, chunk: int = 128,
        interpret: Optional[bool] = None):
    """SSD scan: Pallas intra-chunk kernel + host inter-chunk recurrence.

    Same contract as repro.kernels.ref.ssd_reference.
    Returns (y (B,S,H,P), final_state (B,H,P,N) f32).
    """
    y, hT = _ssd_fwd_impl(x, dt, A, B_, C_, chunk, interpret)
    return y, hT


def _ssd_fwd_impl(x, dt, A, B_, C_, chunk, interpret):
    Bsz, S, H, P = x.shape
    N = B_.shape[-1]
    Q = min(chunk, S)
    nc = S // Q
    y_intra, states, dall, dchunk = _ssd.ssd_chunk_kernel(
        x, dt, A, B_, C_, chunk=Q, interpret=_auto_interpret(interpret))
    Cr = C_.astype(jnp.float32).reshape(Bsz, nc, Q, N)

    def step(h, inp):
        st_c, dall_c, dch_c, c_c = inp
        # y_inter_i = C_i . (exp(L_i) * h_prev)
        y_int = jnp.einsum("bqn,bhq,bhpn->bqhp", c_c, dall_c, h)
        h_new = h * dch_c[..., None, None] + st_c
        return h_new, y_int

    h0 = jnp.zeros((Bsz, H, P, N), jnp.float32)
    hT, y_inter = jax.lax.scan(
        step, h0,
        (states.transpose(2, 0, 1, 3, 4), dall.transpose(2, 0, 1, 3),
         dchunk.transpose(2, 0, 1), Cr.transpose(1, 0, 2, 3)))
    y_inter = y_inter.transpose(1, 0, 2, 3, 4).reshape(Bsz, S, H, P)
    y = (y_intra.reshape(Bsz, S, H, P) + y_inter).astype(x.dtype)
    return y, hT


def _ssd_fwd(x, dt, A, B_, C_, chunk, interpret):
    out = _ssd_fwd_impl(x, dt, A, B_, C_, chunk, interpret)
    return out, (x, dt, A, B_, C_)


def _ssd_bwd(chunk, interpret, res, cts):
    # backward through the jnp reference (identical math; recomputes chunk
    # terms instead of storing (Q,Q) matrices)
    x, dt, A, B_, C_ = res
    def f(x, dt, A, B_, C_):
        return _ref.ssd_reference(x, dt, A, B_, C_, chunk=chunk)
    _, vjp = jax.vjp(f, x, dt, A, B_, C_)
    return vjp(cts)


ssd.defvjp(_ssd_fwd, _ssd_bwd)
