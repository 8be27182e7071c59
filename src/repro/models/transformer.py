"""Transformer/hybrid assembly: param specs, init, scan-over-layers forward.

The layer stack is expressed as a *program* [(mixer, ffn)] and compiled as a
``lax.scan`` over its repeating period (gemma2: period 2 local/global; jamba:
period 8 = 7 mamba + 1 attn with MoE on odd positions; everything else:
period 1).  Scan keeps HLO size O(1) in depth — a 94-layer qwen3 lowers as a
single group body — which is what makes 80 dry-run compiles tractable.

Weights for sub-layer position j are stacked (G, ...) where G = L / period.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.sharding.partition import NULL_CTX, PartitionRules, ShardCtx

from .attention import AttnCache, attention_layer, attn_params_spec
from .layers import mlp, rms_norm, softcap
from .mamba2 import MambaCache, mamba_layer, mamba_params_spec
from .moe import moe_ffn, moe_params_spec


# ------------------------ differentiable barrier ------------------------ #

@jax.custom_vjp
def _pin(x):
    """``optimization_barrier`` with a VJP: the stock primitive has no
    differentiation rule, so pin the forward residual and the backward
    cotangent explicitly (the barrier must survive AD for remat to work)."""
    return jax.lax.optimization_barrier(x)


def _pin_fwd(x):
    return jax.lax.optimization_barrier(x), None


def _pin_bwd(_, g):
    return (jax.lax.optimization_barrier(g),)


_pin.defvjp(_pin_fwd, _pin_bwd)


# --------------------------- layer program ----------------------------- #

def layer_program(cfg) -> List[Tuple[str, str]]:
    return [(cfg.layer_kind(i), cfg.ffn_kind(i)) for i in range(cfg.num_layers)]


def program_period(cfg) -> int:
    prog = layer_program(cfg)
    L = len(prog)
    for p in range(1, L + 1):
        if L % p == 0 and all(prog[i] == prog[i % p] for i in range(L)):
            return p
    return L


# ----------------------------- param specs ------------------------------ #

def _dense_ffn_spec(cfg, f=None):
    d, f = cfg.d_model, f or cfg.d_ff
    s = {"wi": ((d, f), ("embed_w", "mlp")), "wo": ((f, d), ("mlp", "embed_w"))}
    if cfg.gated_mlp:
        s["wg"] = ((d, f), ("embed_w", "mlp"))
    return s


def sublayer_spec(cfg, mixer: str, ffn: str):
    d = cfg.d_model
    spec: Dict[str, Any] = {"norm1": ((d,), ("embed_w",))}
    if mixer in ("attn", "local_attn"):
        spec["mixer"] = attn_params_spec(cfg)
    else:
        spec["mixer"] = mamba_params_spec(cfg)
    if ffn != "none":
        spec["norm2"] = ((d,), ("embed_w",))
        spec["ffn"] = moe_params_spec(cfg) if ffn == "moe" else _dense_ffn_spec(cfg)
        if ffn == "moe" and cfg.shared_expert_ff:
            spec["shared"] = _dense_ffn_spec(cfg, cfg.shared_expert_ff)
    return spec


def param_specs(cfg):
    """Full spec tree; leaves are (shape, logical_axes)."""
    d, V = cfg.d_model, cfg.vocab_size
    p = program_period(cfg)
    G = cfg.num_layers // p
    spec: Dict[str, Any] = {
        "embed": ((V, d), ("vocab", "embed_w")),
        "final_norm": ((d,), ("embed_w",)),
    }
    if not cfg.tie_embeddings:
        spec["lm_head"] = ((d, V), ("embed_w", "vocab"))
    if cfg.frontend == "vision_stub":
        spec["connector"] = {"wi": ((d, d), ("embed_w", "mlp")),
                             "wo": ((d, d), ("mlp", "embed_w"))}
    prog = layer_program(cfg)
    layers = []
    for j in range(p):
        sub = sublayer_spec(cfg, *prog[j])
        sub = jax.tree.map(
            lambda leaf: ((G,) + leaf[0], ("layers",) + leaf[1]),
            sub, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
            and isinstance(x[0], tuple))
        layers.append(sub)
    spec["layers"] = layers
    return spec


def _is_spec_leaf(x):
    return (isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)
            and all(isinstance(i, int) for i in x[0]))


def abstract_params(cfg, dtype=None):
    dtype = dtype or cfg.dtype
    return jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(leaf[0], jnp.dtype(dtype)),
        param_specs(cfg), is_leaf=_is_spec_leaf)


def param_axes(cfg):
    return jax.tree.map(lambda leaf: leaf[1], param_specs(cfg),
                        is_leaf=_is_spec_leaf)


def param_pspecs(cfg, mesh, rules: Optional[PartitionRules] = None):
    rules = rules or PartitionRules()
    return jax.tree.map(
        lambda leaf: rules.spec_for(leaf[1], leaf[0], mesh),
        param_specs(cfg), is_leaf=_is_spec_leaf)


def init_params(cfg, key, dtype=None):
    """Real initialization (smoke tests / the end-to-end trainer)."""
    dtype = dtype or cfg.dtype
    specs = param_specs(cfg)
    leaves, treedef = jax.tree.flatten(specs, is_leaf=_is_spec_leaf)
    keys = jax.random.split(key, len(leaves))

    def mk(leaf, k):
        shape, axes = leaf
        core = axes[1:] if axes[:1] == ("layers",) else axes
        if core in (("embed_w",), ("ssm_inner",)):  # norm scale (a delta), bias
            return jnp.zeros(shape, dtype)
        if core == ("ssm_heads",):                  # A_log / dt_bias / D
            return jax.random.uniform(k, shape, jnp.float32, 0.5, 1.5)
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        if core and core[0] in ("heads",):          # wo: (H, hd, D) fan_in = H*hd
            fan_in = shape[-3] * shape[-2] if len(shape) >= 3 else fan_in
        scale = 1.0 / math.sqrt(max(1, fan_in))
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    vals = [mk(l, k) for l, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


# ------------------------------- caches -------------------------------- #

def cache_specs(cfg, batch: int, max_seq: int, dtype="bfloat16"):
    """Abstract decode cache tree (matches ``layers`` structure)."""
    p = program_period(cfg)
    G = cfg.num_layers // p
    prog = layer_program(cfg)
    dt = jnp.dtype(dtype)
    out = []
    for j in range(p):
        mixer, _ = prog[j]
        if mixer in ("attn", "local_attn"):
            kv = jax.ShapeDtypeStruct(
                (G, batch, max_seq, cfg.num_kv_heads, cfg.head_dim), dt)
            out.append(AttnCache(kv, kv))
        else:
            out.append(MambaCache(
                jax.ShapeDtypeStruct(
                    (G, batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                    jnp.float32),
                jax.ShapeDtypeStruct(
                    (G, batch, cfg.conv_width - 1,
                     cfg.inner_dim + 2 * cfg.ssm_state), dt)))
    return out


def cache_axes(cfg):
    """Logical axes matching cache_specs leaves."""
    p = program_period(cfg)
    prog = layer_program(cfg)
    out = []
    for j in range(p):
        mixer, _ = prog[j]
        if mixer in ("attn", "local_attn"):
            ax = ("layers", "batch", "seq_kv", "kv_heads", "head_dim")
            out.append(AttnCache(ax, ax))
        else:
            out.append(MambaCache(
                ("layers", "batch", "ssm_heads", None, "state"),
                ("layers", "batch", None, "ssm_inner")))
    return out


def init_cache(cfg, batch: int, max_seq: int, dtype="bfloat16"):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        cache_specs(cfg, batch, max_seq, dtype),
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


# ------------------------------- forward ------------------------------- #

def _apply_sublayer(cfg, kind, ffn, w, x, *, sctx, positions, cache, pos,
                    use_pallas):
    h = rms_norm(x, w["norm1"], cfg.norm_eps)
    if kind in ("attn", "local_attn"):
        mix, new_cache = attention_layer(
            cfg, w["mixer"], h, local=(kind == "local_attn"), sctx=sctx,
            positions=positions, cache=cache, pos=pos, use_pallas=use_pallas)
    else:
        mix, new_cache = mamba_layer(cfg, w["mixer"], h, sctx=sctx,
                                     cache=cache, use_pallas=use_pallas)
    r = cfg.residual_multiplier
    x = x + (mix * r if r != 1.0 else mix)
    aux = jnp.zeros((), jnp.float32)
    if ffn != "none":
        h = rms_norm(x, w["norm2"], cfg.norm_eps)
        if ffn == "moe":
            out, aux = moe_ffn(h, w["ffn"], cfg, sctx)
            if cfg.shared_expert_ff:
                out = out + mlp(h, w["shared"], cfg.gated_mlp)
        else:
            out = mlp(h, w["ffn"], cfg.gated_mlp)
            out = sctx.act(out, ("batch", "seq", None))
        x = x + (out * r if r != 1.0 else out)
    return x, new_cache, aux


def forward(cfg, params, embeds, *, mode: str = "train",
            sctx: ShardCtx = NULL_CTX, positions=None, cache=None, pos=None,
            use_pallas=False, remat: Optional[str] = None):
    """Run the layer stack.  embeds: (B, S, D).

    mode: "train" (no caches), "prefill" (emit caches), "decode" (cache
    in/out, S == 1, ``pos`` = write index).
    Returns (hidden (B,S,D), new_cache_or_None, aux_loss scalar).
    """
    prog = layer_program(cfg)
    p = program_period(cfg)
    remat = cfg.remat if remat is None else remat
    x = embeds

    policy = {"full": jax.checkpoint_policies.nothing_saveable,
              "dots": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
              "none": None}[remat]

    def make_sub(j):
        kind, ffn = prog[j]

        def sub(x, wj, cj):
            return _apply_sublayer(
                cfg, kind, ffn, wj, x, sctx=sctx, positions=positions,
                cache=cj, pos=pos, use_pallas=use_pallas)
        # two-level remat: the outer checkpoint on the scanned group keeps
        # scan residuals to one carry per group; the inner per-sublayer
        # checkpoint keeps the group's backward to one sublayer's interior
        # at a time (crucial for jamba's 8-sublayer groups).
        if mode == "train" and policy is not None and p > 1:
            return jax.checkpoint(sub, policy=policy)
        return sub

    subs = [make_sub(j) for j in range(p)]

    def group_body(x, ws, cs):
        # Barrier pins the scan residual to the bf16 carry itself: without
        # it XLA CSEs rms_norm's f32 upcast into the saved residual,
        # doubling layer-boundary checkpoint memory.
        x = _pin(x)
        new_caches = []
        aux_total = jnp.zeros((), jnp.float32)
        for j in range(p):
            cj = cs[j] if cs is not None else None
            x, nc, aux = subs[j](x, ws[j], cj)
            new_caches.append(nc)
            aux_total = aux_total + aux
        return x, tuple(new_caches), aux_total

    if mode == "train" and policy is not None:
        group_body = jax.checkpoint(group_body, policy=policy)

    ws_stacked = tuple(params["layers"])   # tuple over j of stacked trees

    if mode == "train":
        def body(c, w):
            c, _, aux = group_body(c, w, None)
            return c, aux
        x, auxs = jax.lax.scan(body, x, ws_stacked)
        new_cache = None
    elif mode == "prefill":
        def body(c, w):
            c, ncs, aux = group_body(c, w, None)
            return c, (ncs, aux)
        x, (new_cache, auxs) = jax.lax.scan(body, x, ws_stacked)
        new_cache = list(new_cache)
    elif mode == "decode":
        def body(c, wc):
            w, cs = wc
            c, ncs, aux = group_body(c, w, cs)
            return c, (ncs, aux)
        x, (new_cache, auxs) = jax.lax.scan(body, x, (ws_stacked, tuple(cache)))
        new_cache = list(new_cache)
    else:
        raise ValueError(mode)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, new_cache, jnp.sum(auxs)
