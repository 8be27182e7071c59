"""Top-k Mixture-of-Experts: capacity-based (GShard/Switch lineage) or
dropless.

Three dispatch modes (``cfg.moe_dispatch``):

* ``einsum``  — classic TPU one-hot dispatch/combine einsums at a capacity.
  Baseline; matches what TPU MoE systems of the paper's era actually ran.
  Its one-hot matmuls are counted (and executed) as real MXU FLOPs.
* ``gather``  — zero-FLOP dispatch at the same capacity: token->slot indices
  built with a cumsum + scatter, tokens moved by gather, combined by gather.
* ``ragged``  — dropless: every (token, expert) assignment to an expert the
  layer holds is computed.  The assignments are sorted by expert, their
  rows gathered and multiplied by ``jax.lax.ragged_dot``, one group of rows
  per expert, so the expert FLOPs follow the rows routed here.

The capacity modes process tokens in groups so the dispatch tensors stay
VMEM-sized; experts are sharded on the ``model`` mesh axis (EP), token
groups on ``data`` — the cross product is the all-to-all the XLA
partitioner inserts.

A ``ragged`` layer may hold a contiguous block of the experts
(``cfg.moe_held`` of them from ``cfg.moe_first``), one chip's share under
expert parallelism: it routes over all ``num_experts`` and gives its own
experts' part of the result, leaving the absent experts' part to the chips
that hold them.  The layer is one trace-time count of
``moe.dispatch.ragged`` (``repro.core.tracing``) and the scopes ``moe``
(router, top-k, sort, gathers, combine) and ``experts`` inside it (the
grouped matmuls).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import tracing


def moe_params_spec(cfg):
    # "expert_ff" is unsharded by default (FSDP handles the d dim); the
    # decode-optimized rule set maps it to the data axis (2D expert-TP, so
    # weights are never all-gathered at serving time).
    d, f, e = cfg.d_model, cfg.d_ff, cfg.experts_held
    spec = {
        "router": ((d, cfg.num_experts), ("embed_w", None)),
        "wi": ((e, d, f), ("expert", "expert_embed", "expert_ff")),
        "wo": ((e, f, d), ("expert", "expert_ff", "expert_embed")),
    }
    if cfg.gated_mlp:
        spec["wg"] = ((e, d, f), ("expert", "expert_embed", "expert_ff"))
    return spec


def _route(x, router_w, cfg):
    """x: (G, T, D) -> gates (G, T, k), idx (G, T, k)."""
    logits = jnp.einsum("gtd,de->gte", x, router_w.astype(x.dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # load-balancing auxiliary loss (Switch): E * sum(frac_tokens * frac_prob)
    E = cfg.num_experts
    me = jnp.mean(jax.nn.one_hot(idx[..., 0], E, dtype=jnp.float32), axis=(0, 1))
    ce = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(me * ce)
    return gates, idx, aux


def _positions(idx, E, C):
    """Slot position of each (token, k) assignment within its expert.

    idx: (G, T, k) int. Returns pos (G, T, k) int (>= C means dropped).
    Priority: slot order then token order (GShard).
    """
    G, T, K = idx.shape
    flat = idx.transpose(0, 2, 1).reshape(G, K * T)          # k-major priority
    onehot = jax.nn.one_hot(flat, E, dtype=jnp.int32)        # (G, KT, E)
    pos_flat = jnp.cumsum(onehot, axis=1) - 1                # (G, KT, E)
    pos_flat = jnp.take_along_axis(pos_flat, flat[..., None], axis=2)[..., 0]
    return pos_flat.reshape(G, K, T).transpose(0, 2, 1)      # (G, T, k)


def _expert_ffn(xe, w, gated):
    """xe: (G, E, C, D) -> (G, E, C, D) through per-expert MLP."""
    h = jnp.einsum("gecd,edf->gecf", xe, w["wi"])
    if gated:
        g = jnp.einsum("gecd,edf->gecf", xe, w["wg"])
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h)
    return jnp.einsum("gecf,efd->gecd", h, w["wo"])


@jax.named_scope("moe")
def dropless_moe(x, w, cfg):
    """x: (B, S, D) -> (the held experts' part of the output, aux_loss)."""
    tracing.count("moe.dispatch.ragged")
    B, S, D = x.shape
    T, K, held = B * S, cfg.num_experts_per_tok, cfg.experts_held
    xt = x.reshape(T, D)
    gates, idx, aux = _route(xt[None], w["router"], cfg)     # (1, T, K)
    local = idx[0] - cfg.moe_first
    mine = (local >= 0) & (local < held)                     # (T, K)
    key = jnp.where(mine, local, held).reshape(T * K)        # held: not here
    order = jnp.argsort(key, stable=True)
    # a token takes an expert at most once, so at most min(K, held) of its
    # assignments are here: the first M rows by expert hold all of them
    M = T * min(K, held)
    rows = jnp.take(xt, order[:M] // K, axis=0)              # (M, D)
    sizes = jnp.bincount(key, length=held + 1)[:held]
    with jax.named_scope("experts"):
        h = jax.lax.ragged_dot(rows, w["wi"], sizes)
        if cfg.gated_mlp:
            h = jax.nn.silu(jax.lax.ragged_dot(rows, w["wg"], sizes)) * h
        else:
            h = jax.nn.gelu(h)
        y = jax.lax.ragged_dot(h, w["wo"], sizes)            # (M, D)
    # each assignment's row of y; those not here read the zero row M
    rank = jnp.zeros((T * K,), jnp.int32).at[order].set(
        jnp.arange(T * K, dtype=jnp.int32))
    at = jnp.where(mine.reshape(T * K), rank, M)
    y = jnp.concatenate([y, jnp.zeros((1, D), y.dtype)])
    yk = jnp.take(y, at, axis=0).reshape(T, K, D)
    out = jnp.einsum("tkd,tk->td", yk, (gates[0] * mine).astype(y.dtype))
    return out.reshape(B, S, D), aux


def moe_ffn(x, w, cfg, sctx, group_size: int = 4096):
    """x: (B, S, D) -> (B, S, D).  Returns (out, aux_loss)."""
    if cfg.moe_dispatch == "ragged":
        out, aux = dropless_moe(x, w, cfg)
        return sctx.act(out, ("batch", "seq", None)), aux
    if cfg.experts_held != cfg.num_experts:
        raise ValueError("a layer that holds a share of the experts needs "
                         "moe_dispatch='ragged'")
    B, S, D = x.shape
    T = B * S
    g = max(1, T // min(group_size, T))
    Tg = T // g
    xg = x.reshape(g, Tg, D)
    xg = sctx.act(xg, ("batch", None, None))

    E, K = cfg.num_experts, cfg.num_experts_per_tok
    C = int(-(-Tg * K * cfg.capacity_factor // E))           # ceil
    C = max(4, (C + 3) // 4 * 4)
    C = min(C, Tg * K)

    gates, idx, aux = _route(xg, w["router"], cfg)
    pos = _positions(idx, E, C)                              # (G, T, k)
    keep = (pos < C)
    gates = gates * keep

    if cfg.moe_dispatch == "einsum":
        # dispatch (G, T, E, C) one-hot; combine = dispatch * per-token gate
        oh_e = jax.nn.one_hot(idx, E, dtype=xg.dtype)                  # (G,T,k,E)
        oh_c = jax.nn.one_hot(jnp.where(keep, pos, C), C + 1,
                              dtype=xg.dtype)[..., :-1]                # (G,T,k,C)
        disp = jnp.einsum("gtke,gtkc->gtec", oh_e, oh_c)
        disp = sctx.act(disp, ("batch", None, "expert", None))
        xe = jnp.einsum("gtec,gtd->gecd", disp, xg)
        xe = sctx.act(xe, ("batch", "expert", None, None))
        ye = _expert_ffn(xe, w, cfg.gated_mlp)
        ye = sctx.act(ye, ("batch", "expert", None, None))
        comb = jnp.einsum("gtke,gtkc,gtk->gtec", oh_e, oh_c,
                          gates.astype(xg.dtype))
        comb = sctx.act(comb, ("batch", None, "expert", None))
        out = jnp.einsum("gtec,gecd->gtd", comb, ye)
    else:  # gather dispatch: zero-FLOP data movement
        tok = jnp.broadcast_to(jnp.arange(Tg)[None, :, None], idx.shape)
        slot_src = jnp.full((g, E, C), Tg, jnp.int32)        # Tg = "no token"
        # a dropped assignment writes nowhere: its expert index E is out of
        # bounds, so it cannot race a kept token for a slot
        slot_src = slot_src.at[
            jnp.arange(g)[:, None, None],
            jnp.where(keep, idx, E),
            jnp.where(keep, pos, 0)].set(tok, mode="drop")
        xpad = jnp.concatenate([xg, jnp.zeros((g, 1, D), xg.dtype)], axis=1)
        xe = jnp.take_along_axis(
            xpad, slot_src.reshape(g, E * C)[..., None],
            axis=1).reshape(g, E, C, D)
        xe = sctx.act(xe, ("batch", "expert", None, None))
        ye = _expert_ffn(xe, w, cfg.gated_mlp)
        ypad = ye.reshape(g, E * C, D)
        flat_slot = idx * C + jnp.where(keep, pos, 0)        # (G, T, k)
        yk = jnp.take_along_axis(ypad, flat_slot.reshape(g, Tg * K)[..., None],
                                 axis=1).reshape(g, Tg, K, D)
        out = jnp.einsum("gtkd,gtk->gtd", yk, (gates * keep).astype(yk.dtype))

    out = out.reshape(B, S, D)
    return sctx.act(out, ("batch", "seq", None)), aux
