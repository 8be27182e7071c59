"""GQA attention: flash-style (train/prefill) + KV-cache decode.

``flash_attention`` is the one entry for attention over a whole sequence:
on a TPU, with q at the static offset 0 and a causal mask, it runs the
fused Pallas kernel (``repro.kernels.ops.splash_attention``); otherwise
``blockwise_attention``.  ``attention_path`` makes that choice from what
the call can observe, and ``repro.core.tracing`` counts it at trace time
(``attention.kernel``, ``attention.jnp.<reason>``).

The blockwise implementation is the pure-JAX statement of the flash
algorithm (online softmax over KV blocks via ``lax.scan``): it is the
compile-anywhere path used by the dry-run and on the CPU, the path of
sequence-parallel q chunks, and an oracle for the kernels.  Memory is
O(S * block_k) instead of O(S^2), which is what makes the 32k-prefill
cells lowerable.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import tracing

from .layers import apply_rope, softcap

NEG_INF = -1e30


def _mesh_axes(mesh):
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    db = 1
    for a in batch_axes:
        db *= mesh.shape[a]
    m = mesh.shape.get("model", 1)
    return batch_axes, db, m


def _mask_block(q_pos, kv_pos, *, causal: bool, window: int):
    """(Sq, Bk) boolean mask for one KV block."""
    m = jnp.ones((q_pos.shape[0], kv_pos.shape[0]), dtype=bool)
    if causal:
        m &= kv_pos[None, :] <= q_pos[:, None]
    if window:
        m &= kv_pos[None, :] > (q_pos[:, None] - window)
    return m


def _block_scores(qg, kblk, q_pos, kv_pos, *, causal, window, cap, scale):
    """Masked (possibly soft-capped) scores for one KV block, f32."""
    s = jnp.einsum("bshgd,bkhd->bshgk", qg, kblk,
                   preferred_element_type=jnp.float32) * scale
    if cap:
        s = softcap(s, cap)
    mask = _mask_block(q_pos, kv_pos, causal=causal, window=window)
    s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
    return s, mask


def _blockify(k, block_k):
    B, Skv, Hkv, D = k.shape
    nblk = (Skv + block_k - 1) // block_k
    pad = nblk * block_k - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return k.reshape(B, nblk, block_k, Hkv, D), nblk, pad


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def blockwise_attention(q, k, v, q_offset, causal: bool = True,
                        window: int = 0, attn_softcap: float = 0.0,
                        block_k: int = 512, block_q: int = 512):
    """Flash attention in pure JAX: q (B,Sq,Hq,D) x k/v (B,Skv,Hkv,D).

    Double-blocked (q x kv nested scans): transients are O(block_q*block_k),
    never O(S^2) or O(S*block).  Backward is a custom VJP that saves only
    (q,k,v,out,lse) and recomputes block scores — the flash algorithm stated
    in jnp, and the oracle the Pallas TPU kernel is validated against.

    ``q_offset`` (int32 scalar array, traced) is the absolute position of
    q[:, 0] — nonzero under sequence-parallel attention where each model
    shard owns a contiguous q chunk.
    """
    out, _ = _flash_fwd(q, k, v, q_offset, causal, window, attn_softcap,
                        block_k, block_q)
    return out


def _qblockify(q, block_q):
    B, Sq, Hkv, G, D = q.shape
    nq = (Sq + block_q - 1) // block_q
    pad = nq * block_q - Sq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
    return q.reshape(B, nq, block_q, Hkv, G, D), nq, pad


def _flash_fwd(q, k, v, q_offset, causal, window, cap, block_k, block_q):
    B, Sq, Hq, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = D ** -0.5
    qb, nq, qpad = _qblockify(q.reshape(B, Sq, Hkv, G, D), block_q)
    kb, nk, _ = _blockify(k, block_k)
    vb, _, _ = _blockify(v, block_k)

    def q_step(_, qs):
        qblk, qi = qs                                  # (B,bq,Hkv,G,D)
        q_pos = q_offset + qi * block_q + jnp.arange(block_q)

        def kv_step(carry, blk):
            m_i, l_i, acc = carry
            kblk, vblk, ki = blk
            kv_pos = ki * block_k + jnp.arange(block_k)
            s, _ = _block_scores(qblk, kblk, q_pos, kv_pos, causal=causal,
                                 window=window, cap=cap, scale=scale)
            s = jnp.where((kv_pos < Skv)[None, None, None, None, :], s, NEG_INF)
            m_new = jnp.maximum(m_i, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_i - m_new)
            l_new = l_i * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bshgk,bkhd->bshgd", p, vblk,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc), None

        m0 = jnp.full((B, block_q, Hkv, G), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, block_q, Hkv, G), jnp.float32)
        a0 = jnp.zeros((B, block_q, Hkv, G, D), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (kb.swapaxes(0, 1), vb.swapaxes(0, 1), jnp.arange(nk)))
        l = jnp.maximum(l, 1e-30)
        out_blk = (acc / l[..., None]).astype(q.dtype)
        lse_blk = m + jnp.log(l)
        return None, (out_blk, lse_blk)

    _, (ob, lseb) = jax.lax.scan(q_step, None, (qb.swapaxes(0, 1),
                                                jnp.arange(nq)))
    out = ob.swapaxes(0, 1).reshape(B, nq * block_q, Hq, D)[:, :Sq]
    lse = lseb.swapaxes(0, 1).reshape(B, nq * block_q, Hkv, G)[:, :Sq]
    return out, (q, k, v, q_offset, out, lse)


def _flash_fwd_vjp(q, k, v, q_offset, causal, window, cap, block_k, block_q):
    out, res = _flash_fwd(q, k, v, q_offset, causal, window, cap, block_k,
                          block_q)
    return out, res


def _flash_bwd(causal, window, cap, block_k, block_q, res, dout):
    q, k, v, q_offset, out, lse = res
    B, Sq, Hq, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = D ** -0.5
    og = (out.astype(jnp.float32) * dout.astype(jnp.float32)) \
        .reshape(B, Sq, Hkv, G, D).sum(axis=-1)            # delta (B,Sq,Hkv,G)
    qb, nq, qpad = _qblockify(q.reshape(B, Sq, Hkv, G, D), block_q)
    dogb, _, _ = _qblockify(dout.reshape(B, Sq, Hkv, G, D), block_q)
    deltab = jnp.pad(og, ((0, 0), (0, qpad), (0, 0), (0, 0))) \
        .reshape(B, nq, block_q, Hkv, G)
    lseb = jnp.pad(lse, ((0, 0), (0, qpad), (0, 0), (0, 0)),
                   constant_values=NEG_INF).reshape(B, nq, block_q, Hkv, G)
    kb, nk, kpad = _blockify(k, block_k)
    vb, _, _ = _blockify(v, block_k)

    # outer scan over KV blocks (ys -> dk/dv blocks); inner over q blocks
    # (carry accumulates dq into a full-size f32 buffer by slice updates).
    def kv_step(dq_full, blk):
        kblk, vblk, ki = blk
        kv_pos = ki * block_k + jnp.arange(block_k)

        def q_step(carry, qs):
            dq_full = carry
            qblk, dogblk, lse_blk, delta_blk, qi = qs
            lq = qi * block_q + jnp.arange(block_q)
            q_pos = q_offset + lq
            sraw = jnp.einsum("bshgd,bkhd->bshgk", qblk, kblk,
                              preferred_element_type=jnp.float32) * scale
            s = softcap(sraw, cap) if cap else sraw
            mask = _mask_block(q_pos, kv_pos, causal=causal, window=window)
            mask &= (kv_pos < Skv)[None, :]
            mask &= (lq < Sq)[:, None]
            s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
            p = jnp.exp(s - lse_blk[..., None])
            dvb = jnp.einsum("bshgk,bshgd->bkhd", p,
                             dogblk.astype(jnp.float32))
            dp = jnp.einsum("bshgd,bkhd->bshgk", dogblk, vblk,
                            preferred_element_type=jnp.float32)
            ds = p * (dp - delta_blk[..., None])
            if cap:
                ds = ds * (1.0 - jnp.square(s / cap))      # tanh chain rule
            ds = jnp.where(mask[None, :, None, None, :], ds, 0.0) * scale
            dq_blk = jnp.einsum("bshgk,bkhd->bshgd", ds, kblk)
            dkb = jnp.einsum("bshgk,bshgd->bkhd", ds,
                             qblk.astype(jnp.float32))
            start = qi * block_q
            prev = jax.lax.dynamic_slice_in_dim(dq_full, start, block_q, 1)
            dq_full = jax.lax.dynamic_update_slice_in_dim(
                dq_full, prev + dq_blk.reshape(B, block_q, Hq, D), start, 1)
            return dq_full, (dkb, dvb)

        dq_full, (dkbs, dvbs) = jax.lax.scan(
            q_step, dq_full,
            (qb.swapaxes(0, 1), dogb.swapaxes(0, 1), lseb.swapaxes(0, 1),
             deltab.swapaxes(0, 1), jnp.arange(nq)))
        return dq_full, (dkbs.sum(axis=0), dvbs.sum(axis=0))

    dq0 = jnp.zeros((B, nq * block_q, Hq, D), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(
        kv_step, dq0, (kb.swapaxes(0, 1), vb.swapaxes(0, 1), jnp.arange(nk)))
    dk = dks.swapaxes(0, 1).reshape(B, nk * block_k, Hkv, D)[:, :Skv]
    dv = dvs.swapaxes(0, 1).reshape(B, nk * block_k, Hkv, D)[:, :Skv]
    d_offset = np.zeros((), jax.dtypes.float0)        # int arg: no gradient
    return (dq[:, :Sq].astype(q.dtype), dk.astype(k.dtype),
            dv.astype(v.dtype), d_offset)


blockwise_attention.defvjp(_flash_fwd_vjp, _flash_bwd)


def attention_path(q_offset, causal: bool = True) -> tuple:
    """("kernel", "") where the fused kernel runs the call, else ("jnp",
    reason): "backend" off a TPU, "q_offset" where q does not start at the
    static position 0 (sequence-parallel chunks), "mask" where the mask is
    not causal (padding to the kernel's blocks relies on causality).  The
    kernel takes every sliding window and soft cap."""
    from repro.kernels import ops as kops  # Pallas takes ~1.6 s to import
    if not kops.on_tpu():
        return "jnp", "backend"
    if not (isinstance(q_offset, int) and q_offset == 0):
        return "jnp", "q_offset"
    if not causal:
        return "jnp", "mask"
    return "kernel", ""


def flash_attention(q, k, v, q_offset=0, *, causal: bool = True,
                    window: int = 0, attn_softcap: float = 0.0):
    """Attention of q (B,Sq,Hq,D) over k/v (B,Skv,Hkv,D): the fused kernel
    or ``blockwise_attention``, as ``attention_path`` decides, counted.
    ``q_offset`` is the Python int 0 or a traced position of q[:, 0]."""
    path, reason = attention_path(q_offset, causal)
    tracing.count(f"attention.{path}.{reason}" if reason
                  else f"attention.{path}")
    if path == "kernel":
        from repro.kernels import ops as kops
        return kops.splash_attention(q, k, v, window=window,
                                     attn_softcap=attn_softcap)
    if isinstance(q_offset, int):
        q_offset = jnp.full((), q_offset, jnp.int32)
    return blockwise_attention(q, k, v, q_offset, causal, window,
                               attn_softcap)


def decode_attention(q, k_cache, v_cache, pos, *, window: int = 0,
                     attn_softcap: float = 0.0):
    """Single-token attention against a cache.

    q: (B, 1, Hq, D); caches: (B, S, Hkv, D); pos: scalar index of the new
    token (cache already contains it at ``pos``).
    """
    B, _, Hq, D = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bshd->bhgs", qg, k_cache,
                   preferred_element_type=jnp.float32) * (D ** -0.5)
    if attn_softcap:
        s = softcap(s, attn_softcap)
    kv_pos = jnp.arange(S)
    mask = kv_pos <= pos
    if window:
        mask &= kv_pos > (pos - window)
    s = jnp.where(mask[None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p.astype(v_cache.dtype), v_cache)
    return out.reshape(B, 1, Hq, D).astype(q.dtype)


# ---------------------- sharded attention wrappers ---------------------- #

def tp_strategy(S: int, Hq: int, Hkv: int, M: int) -> str:
    """``sharded_flash_attention``'s strategy for a model axis of M."""
    if M <= 1:
        return "local"
    if Hkv % M == 0:
        return "kv_heads"
    if Hq % M == 0 and (Hq // Hkv) % (Hq // M) == 0:
        return "q_heads"
    if S % M == 0:
        return "seq"
    return "local"


def sharded_flash_attention(mesh, q, k, v, *, window: int = 0,
                            attn_softcap: float = 0.0, rules=None):
    """shard_map'd flash attention; picks the TP strategy per shape.

    Strategy (with M = size of the model axis, when not already consumed by
    the batch rule — rule variants like pure-DP hand it to batch instead):
      A. Hkv %% M == 0            -> shard KV heads (q folds consistently)
      B. Hq %% M == 0 and each q-head shard maps to ONE kv head
                                  -> shard q heads, slice the kv head locally
                                     (dk/dv psum'd back via the slice VJP)
      C. otherwise                -> sequence-parallel q (each model shard
                                     owns a contiguous q chunk; k/v
                                     replicated; dk/dv psum over model)
    Batch shards over whatever axes the partition rules resolve for it.
    """
    from repro.sharding.partition import PartitionRules
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    rules = rules or PartitionRules()
    bres = tuple(rules.spec_for(("batch",), (B,), mesh))
    bspec = bres[0] if bres else None
    b_axes = (tuple(bspec) if isinstance(bspec, tuple)
              else ((bspec,) if bspec else ()))
    M = 1 if "model" in b_axes else mesh.shape.get("model", 1)
    strategy = tp_strategy(S, Hq, Hkv, M)

    attend = partial(flash_attention, window=window, attn_softcap=attn_softcap)
    if strategy == "local" and bspec is None:
        return attend(q, k, v)

    if strategy in ("local", "kv_heads"):
        hspec = "model" if strategy == "kv_heads" else None
        fn = shard_map(
            attend,
            mesh=mesh,
            in_specs=(P(bspec, None, hspec, None),) * 3,
            out_specs=P(bspec, None, hspec, None), check_vma=False)
        return fn(q, k, v)

    if strategy == "q_heads":
        Hq_l = Hq // M

        def local(q_, k_, v_):
            m = jax.lax.axis_index("model")
            kv_idx = (m * Hq_l) // G       # the single kv head this shard uses
            k1 = jax.lax.dynamic_slice_in_dim(k_, kv_idx, 1, axis=2)
            v1 = jax.lax.dynamic_slice_in_dim(v_, kv_idx, 1, axis=2)
            return attend(q_, k1, v1)

        fn = shard_map(
            local, mesh=mesh,
            in_specs=(P(bspec, None, "model", None),
                      P(bspec, None, None, None), P(bspec, None, None, None)),
            out_specs=P(bspec, None, "model", None), check_vma=False)
        return fn(q, k, v)

    # strategy == "seq": sequence-parallel q chunks
    S_l = S // M

    def local(q_, k_, v_):
        return attend(q_, k_, v_, jax.lax.axis_index("model") * S_l)

    fn = shard_map(
        local, mesh=mesh,
        in_specs=(P(bspec, "model", None, None),
                  P(bspec, None, None, None), P(bspec, None, None, None)),
        out_specs=P(bspec, "model", None, None), check_vma=False)
    return fn(q, k, v)


def sharded_decode_attention(mesh, q, k_cache, v_cache, kx, vx, pos, *,
                             window: int = 0, attn_softcap: float = 0.0,
                             rules=None):
    """shard_map'd single-token decode: writes (kx, vx) at ``pos`` then
    attends.  The strategy is DERIVED from the partition rules' resolution
    of the cache's logical axes ("batch","seq_kv","kv_heads","head_dim") —
    so rule-set variants (e.g. sharding the KV sequence on the model axis
    when KV heads don't divide it) propagate here automatically:

      - sharded seq dim  -> flash-style cross-shard merge (pmax/psum);
      - sharded head_dim -> psum over those axes for the scores.

    Returns (out (B,1,Hq,D), new_k_cache, new_v_cache).
    """
    from repro.sharding.partition import PartitionRules
    B, Sc, Hkv, D = k_cache.shape
    Hq = q.shape[2]
    G = Hq // Hkv
    rules = rules or PartitionRules()
    spec = tuple(rules.spec_for(("batch", "seq_kv", "kv_heads", "head_dim"),
                                k_cache.shape, mesh))
    spec = spec + (None,) * (4 - len(spec))
    bspec, seqspec, hspec, dspec = spec
    scale = D ** -0.5

    seq_axes = (tuple(seqspec) if isinstance(seqspec, tuple)
                else ((seqspec,) if seqspec else ()))
    d_axes = (tuple(dspec) if isinstance(dspec, tuple)
              else ((dspec,) if dspec else ()))

    def local(q_, kc, vc, kx_, vx_, pos_):
        S_l = kc.shape[1]
        if seq_axes:
            off = jax.lax.axis_index(seq_axes) * S_l
        else:
            off = jnp.zeros((), jnp.int32)
        idx = pos_ - off
        owns = (idx >= 0) & (idx < S_l)
        idxc = jnp.clip(idx, 0, S_l - 1)
        kc = jnp.where(owns, jax.lax.dynamic_update_slice_in_dim(
            kc, kx_.astype(kc.dtype), idxc, 1), kc)
        vc = jnp.where(owns, jax.lax.dynamic_update_slice_in_dim(
            vc, vx_.astype(vc.dtype), idxc, 1), vc)
        Bl, _, Hkv_l, D_l = kc.shape
        qg = q_.reshape(Bl, Hkv_l, q_.shape[2] // Hkv_l, D_l)
        s = jnp.einsum("bhgd,bshd->bhgs", qg, kc,
                       preferred_element_type=jnp.float32) * scale
        if d_axes:
            s = jax.lax.psum(s, d_axes)
        if attn_softcap:
            s = softcap(s, attn_softcap)
        kv_pos = off + jnp.arange(S_l)
        mask = kv_pos <= pos_
        if window:
            mask &= kv_pos > (pos_ - window)
        s = jnp.where(mask[None, None, None, :], s, NEG_INF)
        m_l = s.max(axis=-1)
        if seq_axes:
            m_g = jax.lax.pmax(m_l, seq_axes)
        else:
            m_g = m_l
        p = jnp.exp(s - m_g[..., None])
        l_l = p.sum(axis=-1)
        acc = jnp.einsum("bhgs,bshd->bhgd", p.astype(vc.dtype), vc)
        acc = acc.astype(jnp.float32)
        if seq_axes:
            l_g = jax.lax.psum(l_l, seq_axes)
            acc = jax.lax.psum(acc, seq_axes)
        else:
            l_g = l_l
        out = (acc / jnp.maximum(l_g[..., None], 1e-30)).astype(q_.dtype)
        return out.reshape(Bl, 1, q_.shape[2], D_l), kc, vc

    cache_spec = P(bspec, seqspec, hspec, dspec)
    new_spec = P(bspec, None, hspec, dspec)
    fn = shard_map(
        local, mesh=mesh,
        in_specs=(new_spec, cache_spec, cache_spec, new_spec, new_spec, P()),
        out_specs=(new_spec, cache_spec, cache_spec), check_vma=False)
    return fn(q, k_cache, v_cache, kx, vx, pos)


# ------------------------- full attention layer ------------------------ #

def attn_params_spec(cfg):
    d, Hq, Hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    return {
        "wq": ((d, Hq, hd), ("embed_w", "heads", "head_dim")),
        "wk": ((d, Hkv, hd), ("embed_w", "kv_heads", "head_dim")),
        "wv": ((d, Hkv, hd), ("embed_w", "kv_heads", "head_dim")),
        "wo": ((Hq, hd, d), ("heads", "head_dim", "embed_w")),
    }


class AttnCache(NamedTuple):
    k: jnp.ndarray        # (B, S, Hkv, D)
    v: jnp.ndarray


@jax.named_scope("attention")
def attention_layer(cfg, w, x, *, local: bool, sctx, positions=None,
                    cache: Optional[AttnCache] = None, pos=None,
                    use_pallas: bool = False):
    """Pre-norm attention mixer.  Returns (out, new_cache).

    Train/prefill: cache is None -> ``flash_attention`` over x itself, and
    (for prefill) the produced K/V are returned as the new cache.
    Decode: cache given, x is (B, 1, D), ``pos`` scalar write index.
    """
    window = cfg.sliding_window if local else 0
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, w["wq"])
    kx = jnp.einsum("bsd,dhk->bshk", x, w["wk"])
    vx = jnp.einsum("bsd,dhk->bshk", x, w["wv"])
    if positions is None:
        positions = (jnp.arange(S) if pos is None else (pos + jnp.zeros((S,), jnp.int32)))
        positions = jnp.broadcast_to(positions, (B, S))
    q = apply_rope(q, positions, cfg.rope_theta)
    kx = apply_rope(kx, positions, cfg.rope_theta)
    q = sctx.act(q, ("batch", "seq", "heads", "head_dim"))

    if cache is None:
        if use_pallas:
            from repro.kernels import ops as kops
            out = kops.flash_attention(q, kx, vx, causal=True, window=window,
                                       attn_softcap=cfg.attn_softcap)
        elif sctx.mesh is not None:
            out = sharded_flash_attention(sctx.mesh, q, kx, vx, window=window,
                                          attn_softcap=cfg.attn_softcap,
                                          rules=sctx.rules)
        else:
            out = flash_attention(q, kx, vx, window=window,
                                  attn_softcap=cfg.attn_softcap)
        new_cache = AttnCache(kx, vx)
    else:
        if sctx.mesh is not None:
            out, kc, vc = sharded_decode_attention(
                sctx.mesh, q, cache.k, cache.v, kx, vx, pos, window=window,
                attn_softcap=cfg.attn_softcap, rules=sctx.rules)
        else:
            kc = jax.lax.dynamic_update_slice_in_dim(
                cache.k, kx.astype(cache.k.dtype), pos, axis=1)
            vc = jax.lax.dynamic_update_slice_in_dim(
                cache.v, vx.astype(cache.v.dtype), pos, axis=1)
            out = decode_attention(q, kc, vc, pos, window=window,
                                   attn_softcap=cfg.attn_softcap)
        new_cache = AttnCache(kc, vc)
    out = jnp.einsum("bshk,hkd->bsd", out, w["wo"])
    return sctx.act(out, ("batch", "seq", None)), new_cache
