"""Plain float32 AdamW with decoupled weight decay and global-norm clipping.

Hyperparameters come from the traffic file's ``optimizer`` entry: learning
rate ``lr`` with a linear warm-up over ``warmup_steps`` and a cosine decay
to 0 at ``total_steps``; ``b1``, ``b2``, ``eps``; decoupled ``weight_decay``
on every parameter except those named in ``no_decay``; the gradient scaled
so that its global norm is at most ``clip_norm``.  Parameters are stored in
``param_dtype`` after each update, as the configuration stores them.
"""
from __future__ import annotations

import math

import jax.numpy as jnp


def lr_at(opt: dict, step: int) -> float:
    base, warm, total = opt["lr"], opt["warmup_steps"], opt["total_steps"]
    if step < warm:
        return base * step / warm
    prog = min(max((step - warm) / max(1, total - warm), 0.0), 1.0)
    return 0.5 * base * (1.0 + math.cos(math.pi * prog))


def init(params: dict) -> dict:
    zeros = {k: jnp.zeros(v.shape, jnp.float32) for k, v in params.items()}
    return {"step": 0, "m": zeros, "v": dict(zeros)}


def clip_scale(opt: dict, grads: dict):
    """The factor that brings the gradient's global norm to ``clip_norm``."""
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in grads.values()))
    return jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-9))


def update(opt: dict, params: dict, grads: dict, state: dict):
    """One step, leaf by leaf and in place (each old leaf is dropped as its
    new one is made, so one leaf at a time is held twice).  ``grads`` are
    the raw gradients; they are consumed."""
    scale = clip_scale(opt, grads)
    t = state["step"] = state["step"] + 1
    b1, b2 = opt["b1"], opt["b2"]
    lr = lr_at(opt, t)
    dtype = jnp.dtype(opt["param_dtype"])
    for k in list(params):
        g = grads.pop(k) * scale
        m = state["m"][k] = b1 * state["m"][k] + (1 - b1) * g
        v = state["v"][k] = b2 * state["v"][k] + (1 - b2) * jnp.square(g)
        u = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + opt["eps"])
        if k not in opt["no_decay"]:
            u = u + opt["weight_decay"] * params[k]
        params[k] = (params[k] - lr * u).astype(dtype).astype(jnp.float32)
