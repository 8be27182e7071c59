"""LM assembly: embeddings + stack + loss; step-function factories.

``make_train_step`` / ``make_prefill_step`` / ``make_decode_step`` produce
plain functions over (params, batch, ...) suitable for ``jax.jit`` with
explicit in/out shardings — these are the *task bodies* the workflow runtime
(repro.core) schedules, and the functions the multi-pod dry-run lowers.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.sharding.partition import NULL_CTX, PartitionRules, ShardCtx

from . import transformer
from .layers import mlp, rms_norm, softcap


# ----------------------------- embeddings ------------------------------ #

@jax.named_scope("embed")
def embed_inputs(cfg, params, batch, sctx: ShardCtx = NULL_CTX):
    """Token (+ stub-frontend) embedding.  Returns (B, S_total, D) embeds."""
    emb = params["embed"]
    tok = batch["tokens"]
    x = jnp.take(emb, tok, axis=0).astype(jnp.dtype(cfg.dtype))
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    if cfg.frontend == "vision_stub" and "patches" in batch:
        p = batch["patches"].astype(x.dtype)
        w = params["connector"]
        p = jnp.einsum("bnd,df->bnf", p, w["wi"])
        p = jax.nn.gelu(p)
        p = jnp.einsum("bnf,fd->bnd", p, w["wo"])
        x = jnp.concatenate([p, x], axis=1)
    return sctx.act(x, ("batch", "seq", None))


@jax.named_scope("loss")
def lm_logits(cfg, params, hidden):
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", hidden, head.astype(hidden.dtype))
    if cfg.logits_scaling != 1.0:
        logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
    return softcap(logits, cfg.logit_softcap)


# ------------------------------- loss ---------------------------------- #

def _xent_block(cfg, params, hidden, targets, mask):
    logits = lm_logits(cfg, params, hidden).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    return nll.sum(), mask.sum()


@jax.named_scope("loss")
def lm_loss(cfg, params, hidden, targets, mask, *, chunk: int = 2048):
    """Cross-entropy, chunked along sequence so (B, chunk, V) is the largest
    logits buffer ever live (a production necessity at V=256k)."""
    B, S, D = hidden.shape
    if S <= chunk or S % chunk:
        nll, denom = _xent_block(cfg, params, hidden, targets, mask)
        return nll / jnp.maximum(denom, 1.0)
    nb = S // chunk
    h = hidden.reshape(B, nb, chunk, D).swapaxes(0, 1)
    t = targets.reshape(B, nb, chunk).swapaxes(0, 1)
    m = mask.reshape(B, nb, chunk).swapaxes(0, 1)

    def body(carry, xs):
        hb, tb, mb = xs
        nll, denom = _xent_block(cfg, params, hb, tb, mb)
        return (carry[0] + nll, carry[1] + denom), None

    (nll, denom), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())),
                                   (h, t, m))
    return nll / jnp.maximum(denom, 1.0)


# --------------------------- step factories ----------------------------- #

def loss_fn(cfg, params, batch, sctx: ShardCtx = NULL_CTX, use_pallas=False):
    x = embed_inputs(cfg, params, batch, sctx)
    hidden, _, aux = transformer.forward(
        cfg, params, x, mode="train", sctx=sctx, use_pallas=use_pallas)
    targets, mask = batch["targets"], batch["loss_mask"]
    if cfg.frontend == "vision_stub" and "patches" in batch:
        # patches occupy the prefix; loss only over text positions
        nfe = batch["patches"].shape[1]
        hidden = hidden[:, nfe:]
    loss = lm_loss(cfg, params, hidden, targets, mask)
    if cfg.num_experts and cfg.moe_aux_coef:
        loss = loss + cfg.moe_aux_coef * aux
    return loss, {"loss": loss, "aux": aux}


def make_loss_and_grad(cfg, sctx: ShardCtx = NULL_CTX, use_pallas=False):
    def f(params, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, batch, sctx, use_pallas),
            has_aux=True)(params)
        return grads, metrics
    return f


def make_train_step(cfg, optimizer, sctx: ShardCtx = NULL_CTX,
                    use_pallas=False, microbatches: int = 1,
                    grad_dtype: str = "float32"):
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    ``microbatches`` > 1 enables gradient accumulation: the global batch is
    split along dim 0 and scanned, so layer-boundary activation checkpoints
    are only live for one microbatch (the knob that fits 94-layer models in
    HBM).  Gradients accumulate in ``grad_dtype`` (bf16 for the >=100B archs
    where the f32 buffer alone would blow the per-chip budget).
    """
    def train_step(params, opt_state, batch):
        if microbatches == 1:
            (loss, metrics), grads = jax.value_and_grad(
                lambda p: loss_fn(cfg, p, batch, sctx, use_pallas),
                has_aux=True)(params)
        else:
            dt = jnp.dtype(grad_dtype)
            mb = jax.tree.map(
                lambda x: x.reshape((microbatches, x.shape[0] // microbatches)
                                    + x.shape[1:]), batch)
            if sctx.mesh is not None:
                from repro.models import transformer as _T
                gspecs = _T.param_pspecs(cfg, sctx.mesh, sctx.rules)
                pin = lambda t: jax.tree.map(
                    lambda x, s: jax.lax.with_sharding_constraint(
                        x, jax.NamedSharding(sctx.mesh, s)), t, gspecs)
            else:
                pin = lambda t: t

            def acc_step(carry, mbatch):
                gacc, lacc = carry
                (loss, metrics), g = jax.value_and_grad(
                    lambda p: loss_fn(cfg, p, mbatch, sctx, use_pallas),
                    has_aux=True)(params)
                gacc = pin(jax.tree.map(lambda a, b: a + b.astype(dt),
                                        gacc, g))
                return (gacc, lacc + loss), metrics

            g0 = pin(jax.tree.map(lambda p: jnp.zeros(p.shape, dt), params))
            (gsum, lsum), mstack = jax.lax.scan(
                acc_step, (g0, jnp.zeros((), jnp.float32)), mb)
            grads = jax.tree.map(lambda g: g / microbatches, gsum)
            loss = lsum / microbatches
            metrics = jax.tree.map(lambda m: m.mean(), mstack)
            metrics["loss"] = loss
        params, opt_state, gnorm = optimizer.update(params, grads, opt_state)
        metrics = dict(metrics, grad_norm=gnorm)
        return params, opt_state, metrics
    return train_step


def auto_microbatches(cfg, shape, n_batch_shards: int,
                      target_bytes: float = 4e9) -> int:
    """Pick grad-accumulation depth so layer-boundary checkpoints fit.

    carry bytes = local_batch * seq * d_model * 2 (bf16) * n_groups.
    """
    from repro.models.transformer import program_period
    if shape.kind != "train":
        return 1
    local_b = max(1, shape.global_batch // max(1, n_batch_shards))
    groups = cfg.num_layers // program_period(cfg)
    carry = local_b * shape.seq_len * cfg.d_model * 2 * groups
    need = max(1, int(-(-carry // target_bytes)))
    mu = 1
    while mu < need and mu < local_b and local_b % (mu * 2) == 0:
        mu *= 2
    return mu


def make_prefill_step(cfg, sctx: ShardCtx = NULL_CTX, use_pallas=False):
    """(params, batch) -> (last-token logits, cache)."""
    def prefill_step(params, batch):
        x = embed_inputs(cfg, params, batch, sctx)
        hidden, cache, _ = transformer.forward(
            cfg, params, x, mode="prefill", sctx=sctx, use_pallas=use_pallas)
        logits = lm_logits(cfg, params, hidden[:, -1:])
        return logits, cache
    return prefill_step


def make_decode_step(cfg, sctx: ShardCtx = NULL_CTX, use_pallas=False):
    """(params, token (B,1), cache, pos) -> (logits (B,1,V), new cache)."""
    def decode_step(params, token, cache, pos):
        x = embed_inputs(cfg, params, {"tokens": token}, sctx)
        hidden, cache, _ = transformer.forward(
            cfg, params, x, mode="decode", sctx=sctx, cache=cache, pos=pos,
            use_pallas=use_pallas)
        return lm_logits(cfg, params, hidden), cache
    return decode_step


# ------------------------------ input specs ----------------------------- #

def input_specs(cfg, shape, *, abstract: bool = True) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of a given cell.

    For train/prefill the dict is the `batch`; for decode it is
    {token, cache, pos}.  Frontend stubs contribute precomputed patch
    embeddings (the assignment's modality-stub contract).
    """
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.dtype("int32")
    bf16 = jnp.dtype(cfg.dtype)
    nfe = cfg.frontend_tokens if cfg.frontend == "vision_stub" else 0
    s_text = S - nfe

    def sd(shape_, dt):
        return jax.ShapeDtypeStruct(shape_, dt)

    if shape.kind in ("train", "prefill"):
        spec = {"tokens": sd((B, s_text), i32)}
        if nfe:
            spec["patches"] = sd((B, nfe, cfg.d_model), bf16)
        if shape.kind == "train":
            spec["targets"] = sd((B, s_text), i32)
            spec["loss_mask"] = sd((B, s_text), jnp.dtype("float32"))
        return spec
    # decode: one new token against a seq_len cache
    return {
        "token": sd((B, 1), i32),
        "cache": transformer.cache_specs(cfg, B, S, cfg.dtype),
        "pos": sd((), i32),
    }


def input_axes(cfg, shape) -> Dict[str, Any]:
    """Logical sharding axes matching :func:`input_specs`."""
    if shape.kind in ("train", "prefill"):
        ax = {"tokens": ("batch", "seq")}
        if cfg.frontend == "vision_stub":
            ax["patches"] = ("batch", "seq", None)
        if shape.kind == "train":
            ax["targets"] = ("batch", "seq")
            ax["loss_mask"] = ("batch", "seq")
        return ax
    return {
        "token": ("batch", None),
        "cache": transformer.cache_axes(cfg),
        "pos": (),
    }
