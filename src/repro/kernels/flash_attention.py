"""Pallas TPU flash attention kernel.

Grid: (batch*heads, num_q_blocks, num_kv_blocks) — the last grid dimension
iterates sequentially on TPU, so the online-softmax running state (m, l,
acc) lives in VMEM scratch that persists across kv steps.  BlockSpecs tile
(block_q x head_dim) of q and (block_k x head_dim) of k/v into VMEM;
blocks are MXU-aligned (128-lane).  GQA is resolved in the k/v index_map
(q head -> kv head), so grouped queries reuse K/V tiles without host-side
broadcast.

Causal skipping: kv blocks strictly above the diagonal are predicated off
with pl.when — their MXU work is never issued (the jnp reference pays full
S^2; the kernel pays the ~S^2/2 the algorithm needs).

Validated against repro.kernels.ref.attention_reference in interpret mode
across a shape/dtype sweep (tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale, causal, window, cap, block_q, block_k, nk, sq, skv):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k
    needed = jnp.bool_(True)
    if causal:  # block fully above the diagonal contributes nothing
        needed &= k_start <= q_start + block_q - 1
    if window:  # block fully outside the attention window contributes nothing
        needed &= k_start + block_k - 1 > q_start - window

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32)           # (bq, D)
        k = k_ref[0].astype(jnp.float32)           # (bk, D)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if cap:
            s = cap * jnp.tanh(s / cap)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
        kv_pos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
        mask = (kv_pos < skv) & (q_pos < sq)
        if causal:
            mask &= kv_pos <= q_pos
        if window:
            mask &= kv_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal: bool = True, window: int = 0,
                        attn_softcap: float = 0.0, block_q: int = 128,
                        block_k: int = 128, interpret: bool):
    """q: (B, Sq, Hq, D); k/v: (B, Skv, Hkv, D) -> (B, Sq, Hq, D)."""
    B, Sq, Hq, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    nq = pl.cdiv(Sq, block_q)
    nk = pl.cdiv(Skv, block_k)
    sq_pad, skv_pad = nq * block_q, nk * block_k
    scale = D ** -0.5

    # (B*H, S, D) layout: folded batch*head leading grid dim
    qf = q.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, D)
    if sq_pad != Sq:
        qf = jnp.pad(qf, ((0, 0), (0, sq_pad - Sq), (0, 0)))
    if skv_pad != Skv:
        kf = jnp.pad(kf, ((0, 0), (0, skv_pad - Skv), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, skv_pad - Skv), (0, 0)))

    def kv_index(bh, qi, ki):
        b, h = bh // Hq, bh % Hq
        return (b * Hkv + h // G, ki, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, window=window,
                          cap=attn_softcap, block_q=block_q, block_k=block_k,
                          nk=nk, sq=Sq, skv=Skv),
        grid=(B * Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, D), kv_index),
            pl.BlockSpec((1, block_k, D), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, D),
                               lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, sq_pad, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    out = out[:, :Sq].reshape(B, Hq, Sq, D).transpose(0, 2, 1, 3)
    return out
