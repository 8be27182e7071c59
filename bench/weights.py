"""Weights made by the benchmark from ``--seed``, on the device, in one call.

The program only lays out its parameter tree (``abstract_params``); every
value comes from here, so the reference and the program start from the same
numbers and neither takes them from the other.  The initialisation follows
the published recipes: normal(0, 0.02) for every matrix and the embedding
(the Hugging Face ``initializer_range``); small random RMSNorm offsets (the
scale is stored as its offset from 1), so that the check covers the norms;
for Mamba2, A = U[1, 16], dt = logU[1e-3, 1e-1] through an inverse softplus
into ``dt_bias``, D = 1, and the depthwise convolution uniform in
+-1/sqrt(width).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

STD = 0.02
NORM_STD = 0.02


def seed_key(seed: int):
    """A threefry key from any non-negative integer seed (wider than 32 bits
    too): the seed goes through numpy's SeedSequence."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def path_name(path) -> str:
    out = []
    for k in path:
        out.append(str(getattr(k, "key", getattr(k, "idx", k))))
    return "/".join(out)


def _leaf(name: str, shape, key):
    last = name.rsplit("/", 1)[-1]
    if last in ("norm1", "norm2", "final_norm"):
        return NORM_STD * jax.random.normal(key, shape)
    if last == "A_log":
        return jnp.log(jax.random.uniform(key, shape, minval=1.0, maxval=16.0))
    if last == "D":
        return jnp.ones(shape)
    if last == "dt_bias":
        u = jax.random.uniform(key, shape)
        dt = jnp.exp(u * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        dt = jnp.maximum(dt, 1e-4)
        return dt + jnp.log(-jnp.expm1(-dt))
    if last == "conv_w":
        bound = 1.0 / math.sqrt(shape[-2])
        return jax.random.uniform(key, shape, minval=-bound, maxval=bound)
    return STD * jax.random.normal(key, shape)


def maker(abstract, shardings=None):
    """A jitted ``seed_key -> params`` for the program's abstract tree, laid
    out by ``shardings`` (a tree like it) where given."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    def make(key):
        vals = [_leaf(path_name(p), leaf.shape, jax.random.fold_in(key, i))
                .astype(leaf.dtype) for i, (p, leaf) in enumerate(paths)]
        return jax.tree_util.tree_unflatten(treedef, vals)

    return jax.jit(make, out_shardings=shardings)


def flat(tree) -> dict:
    """{storage path: array}, e.g. ``layers/0/mixer/wq`` (stacked on axis 0)."""
    return {path_name(p): v
            for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]}
