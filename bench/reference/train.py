"""The reference's first training steps, and the numbers a check compares.

``follow`` runs a plain reference model (``transformer`` or ``mamba2``)
through AdamW from the same initial weights and rows as the program, and
returns, for steps 1..n: each step's loss, the per-leaf norms of the first
gradient as the optimizer gets it (after clipping), and the per-leaf norms of
the parameters' change after step n.  A leaf is one layer's slice of a
stacked array, or a whole unstacked array.  Gradients are summed over blocks
of ``rows`` sequences so that the float32 logits and activations fit.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import adamw, mamba2, transformer

MODELS = {"transformer": transformer, "mamba2": mamba2}


def norm_rows(name: str, v):
    """L2 norm of an array, per layer where it is stacked on axis 0."""
    v = v.astype(jnp.float32)
    axes = tuple(range(1 if name.startswith("layers/") else 0, v.ndim))
    return jnp.sqrt(jnp.sum(jnp.square(v), axis=axes))


def expand(norms: dict) -> dict:
    """{leaf: norm}: a stacked array's norms give one leaf per layer."""
    out = {}
    for k, v in norms.items():
        v = np.asarray(v)
        if k.startswith("layers/"):
            out.update({f"{k}#{i}": float(x) for i, x in enumerate(v)})
        else:
            out[k] = float(v)
    return out


def leaf_norms(flat: dict) -> dict:
    """{leaf: L2 norm}; a stacked per-layer array gives one leaf per layer."""
    return expand({k: norm_rows(k, v) for k, v in flat.items()})


@partial(jax.jit, static_argnames=("model", "cfg_items", "prec"))
def _block_grad(weights, tokens, targets, *, model, cfg_items, prec):
    cfg = dict(cfg_items)

    def total_nll(w):
        return MODELS[model].token_nll(w, tokens, targets, cfg, prec).sum()

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(total_nll)(weights)


@partial(jax.jit, donate_argnums=(0,))
def _accumulate(acc, g):
    return jax.tree.map(jnp.add, acc, g)


def loss_and_grads(model, cfg, weights, tokens, targets, prec="f32", rows=1,
                   half=False):
    """Mean token loss and its gradient over all rows, in blocks.  With
    ``half`` (a planted fault) the second half of the rows is left out and
    the mean taken over the rest."""
    cfg_items = tuple(sorted(cfg.items()))
    n_rows, seq = tokens.shape
    used = n_rows // 2 if half else n_rows
    loss, grads = 0.0, None
    for i in range(0, used, rows):
        blk = slice(i, min(i + rows, used))
        l, g = _block_grad(weights, jnp.asarray(tokens[blk]),
                           jnp.asarray(targets[blk]), model=model,
                           cfg_items=cfg_items, prec=prec)
        loss += float(l)
        grads = g if grads is None else _accumulate(grads, g)
        del g
    n_tok = used * seq
    return loss / n_tok, {k: g / n_tok for k, g in grads.items()}


def follow(model, cfg, opt, initial, batches, prec="f32", rows=1,
           half=False):
    """Readings of the reference over ``batches`` [(tokens, targets)].

    ``initial()`` gives the initial weights (a flat dict); it is called
    twice, so that they need not be held while the steps run."""
    params = {k: jnp.asarray(v, jnp.float32) for k, v in initial().items()}
    state = adamw.init(params)
    losses, first_grad = [], None
    for tokens, targets in batches:
        loss, grads = loss_and_grads(model, cfg, params, tokens, targets,
                                     prec, rows, half)
        losses.append(loss)
        if first_grad is None:
            scale = float(adamw.clip_scale(opt, grads))
            first_grad = {k: v * scale for k, v in leaf_norms(grads).items()}
        adamw.update(opt, params, grads, state)
    del state
    start = initial()
    delta = leaf_norms({k: params.pop(k) - jnp.asarray(start.pop(k),
                                                       jnp.float32)
                        for k in list(params)})
    return {"losses": losses, "grad_norms": first_grad, "delta_norms": delta}
