"""The expert layer (``repro.models.moe``): the capacity dispatch at
capacity, and the dropless layer that holds a share of the experts against
the plain float32 reference (``bench/reference/granite_hybrid.py``).

Float32 throughout: program and reference differ only in the order of their
sums (grouped against dense matmuls), about 1e-7 of the output's largest
magnitude; the tolerance, 1e-5 of it, leaves about 100x, and an expert's
part left out or counted twice misses it by orders of magnitude.
"""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))  # bench/

from bench import reference  # noqa: E402
from repro.configs import get_config, reduce_config  # noqa: E402
from repro.models import moe  # noqa: E402
from repro.models.layers import mlp  # noqa: E402
from repro.sharding.partition import NULL_CTX  # noqa: E402

REF = reference.model("granite_hybrid")
TOL = 1e-5


def gap(got, want) -> float:
    want = np.asarray(want)
    return float(np.max(np.abs(np.asarray(got) - want)) / np.max(np.abs(want)))


def layer(E=16, K=3, held=0, first=0, d=32, f=24, seed=0):
    """A ragged expert layer's config and float32 weights for all E experts
    (a share slices its experts out of them)."""
    cfg = dataclasses.replace(
        get_config("granite-4.0-h-small"), d_model=d, d_ff=f, num_experts=E,
        num_experts_per_tok=K, moe_held=held, moe_first=first,
        shared_expert_ff=2 * f, dtype="float32")
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    n = lambda k, s: 0.3 * jax.random.normal(k, s)  # noqa: E731
    w = {"router": n(ks[0], (d, E)), "wi": n(ks[1], (E, d, f)),
         "wg": n(ks[2], (E, d, f)), "wo": n(ks[3], (E, f, d))}
    shared = {"wi": n(ks[4], (d, 2 * f)), "wg": n(ks[5], (d, 2 * f)),
              "wo": n(ks[6], (2 * f, d))}
    return cfg, w, shared


def share(w, first, held):
    return dict(w, **{k: w[k][first:first + held] for k in ("wi", "wg", "wo")})


def reference_part(x, w, cfg):
    """The reference's part of the routed output from ``w``'s experts, the
    first of them being expert ``cfg.moe_first`` of the router."""
    ref_w = {f"ffn/{k}": v for k, v in w.items()}
    return REF.experts(x, ref_w, {"num_experts_per_tok":
                                  cfg.num_experts_per_tok,
                                  "first_expert": cfg.moe_first}, "f32")


def test_gather_dispatch_keeps_every_kept_token_at_capacity():
    """The last expert overflows: tokens past its capacity are dropped, and
    their writes must not land on the slot of a token it kept.  The einsum
    dispatch, which has no scatter, gives the right answer."""
    base = reduce_config(get_config("qwen3-moe-235b-a22b"))
    cfg = dataclasses.replace(base, capacity_factor=1.0)
    d, E, f = cfg.d_model, cfg.num_experts, cfg.d_ff
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    w = {"router": 0.02 * jax.random.normal(ks[0], (d, E)),
         "wi": 0.2 * jax.random.normal(ks[1], (E, d, f)),
         "wg": 0.2 * jax.random.normal(ks[2], (E, d, f)),
         "wo": 0.2 * jax.random.normal(ks[3], (E, f, d))}
    w["router"] = w["router"].at[0, E - 1].set(10.0)
    x = jax.random.normal(ks[4], (2, 8, d)).at[..., 0].set(5.0)
    _, idx, _ = moe._route(x.reshape(1, 16, d), w["router"], cfg)
    assert bool((idx[..., 0] == E - 1).all())      # every token's first pick
    outs = {}
    for mode in ("einsum", "gather"):
        outs[mode], _ = moe.moe_ffn(
            x, w, dataclasses.replace(cfg, moe_dispatch=mode), NULL_CTX)
    assert gap(outs["gather"], outs["einsum"]) < TOL


@pytest.mark.parametrize("first,held", [(0, 16), (4, 4), (14, 2)])
def test_dropless_layer_matches_reference(first, held):
    cfg, w, _ = layer(held=held, first=first)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 12, cfg.d_model))
    got, _ = moe.moe_ffn(x, share(w, first, held), cfg, NULL_CTX)
    want = reference_part(x, share(w, first, held), cfg)
    assert gap(got, want) < TOL


def test_dropless_layer_drops_nothing_when_every_token_picks_one_expert():
    """Adversarial routing: every token's first pick is held expert 5, 24
    rows for it where a capacity layer of factor 1.25 keeps 3."""
    cfg, w, _ = layer(held=4, first=4)
    w["router"] = w["router"].at[0, 5].set(40.0)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 12, cfg.d_model))
    x = x.at[..., 0].set(5.0)
    _, idx, _ = moe._route(x.reshape(1, 24, -1), w["router"], cfg)
    assert bool((idx[..., 0] == 5).all())
    got, _ = moe.moe_ffn(x, share(w, 4, 4), cfg, NULL_CTX)
    want = reference_part(x, share(w, 4, 4), cfg)
    assert gap(got, want) < TOL
    # expert 5 alone, as the reference computes it for every token
    alone = reference_part(x, share(w, 5, 1), dataclasses.replace(
        cfg, moe_first=5))
    assert float(np.min(np.abs(np.asarray(alone)).sum(-1))) > 0


def test_shares_sum_to_the_uncut_layer():
    """Eight chips of 2 experts each: their parts, with the shared expert
    that every chip computes counted once, add up to the uncut reference
    layer (all 16 experts and the shared expert)."""
    cfg, w, shared = layer(E=16, K=3)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 12, cfg.d_model))
    parts = [moe.moe_ffn(x, share(w, 2 * c, 2),
                         dataclasses.replace(cfg, moe_held=2,
                                             moe_first=2 * c), NULL_CTX)[0]
             for c in range(8)]
    got = sum(parts) + mlp(x, shared, True)
    want = reference_part(x, w, cfg) + REF.gated_mlp(
        x, shared["wi"], shared["wg"], shared["wo"], "f32")
    assert gap(got, want) < TOL
    # each chip's part is its own: no two shares give the same output
    assert all(gap(parts[c], parts[c + 1]) > 0.1 for c in range(7))


def test_a_held_share_needs_the_dropless_layer():
    cfg, w, _ = layer(held=4)
    x = jnp.zeros((1, 4, cfg.d_model))
    with pytest.raises(ValueError, match="ragged"):
        moe.moe_ffn(x, share(w, 0, 4),
                    dataclasses.replace(cfg, moe_dispatch="gather"), NULL_CTX)
