"""Per-architecture smoke tests: reduced same-family config, one forward +
one train step + one decode step on CPU; asserts shapes and no NaNs."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS, cells, get_config, reduce_config
from repro.models import model as M
from repro.models import transformer as T
from repro.optim import AdamW


def _batch(cfg, key, B=2, S=16):
    nfe = cfg.frontend_tokens if cfg.frontend == "vision_stub" else 0
    b = {"tokens": jax.random.randint(key, (B, S - nfe), 0, cfg.vocab_size),
         "targets": jax.random.randint(key, (B, S - nfe), 0, cfg.vocab_size),
         "loss_mask": jnp.ones((B, S - nfe))}
    if nfe:
        b["patches"] = jax.random.normal(key, (B, nfe, cfg.d_model),
                                         jnp.bfloat16)
    return b


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_and_train_step(arch):
    cfg = reduce_config(get_config(arch))
    key = jax.random.PRNGKey(0)
    params = T.init_params(cfg, key)
    batch = _batch(cfg, key)
    loss, metrics = M.loss_fn(cfg, params, batch)
    assert loss.shape == ()
    assert not bool(jnp.isnan(loss)), f"{arch}: NaN loss"
    opt = AdamW()
    step = jax.jit(M.make_train_step(cfg, opt))
    p2, o2, m2 = step(params, opt.init(params), batch)
    assert not bool(jnp.isnan(m2["loss"]))
    assert float(m2["grad_norm"]) > 0
    # parameters actually moved
    moved = any(
        float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max()) > 0
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)))
    assert moved, f"{arch}: train step changed nothing"


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_consistency(arch):
    """Greedy decode state built token-by-token matches a fresh prefill."""
    cfg = reduce_config(get_config(arch))
    key = jax.random.PRNGKey(1)
    params = T.init_params(cfg, key)
    B, S = 2, 8
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    nfe = cfg.frontend_tokens if cfg.frontend == "vision_stub" else 0
    if nfe:  # decode-only check for vlm: feed text tokens only
        pass
    prefill = jax.jit(M.make_prefill_step(cfg))
    decode = jax.jit(M.make_decode_step(cfg))
    batch = {"tokens": toks}
    if nfe:
        batch["patches"] = jnp.zeros((B, nfe, cfg.d_model), jnp.bfloat16)
    logits_p, _ = prefill(params, batch)
    assert logits_p.shape == (B, 1, cfg.vocab_size)
    # token-by-token decode over the same prompt (text part only)
    cache = T.init_cache(cfg, B, 32)
    lg = None
    for t in range(S):
        lg, cache = decode(params, toks[:, t:t + 1], cache, jnp.int32(t))
    assert lg.shape == (B, 1, cfg.vocab_size)
    assert not bool(jnp.isnan(lg).any())
    if not nfe:
        # same last-token distribution as prefill (pure-text archs).
        # MoE archs get looser tolerance: capacity-based dropping differs
        # between grouped prefill and single-token decode by design.
        import numpy as np
        tol = 0.25 if cfg.num_experts else 0.1
        np.testing.assert_allclose(
            np.asarray(lg, np.float32), np.asarray(logits_p, np.float32),
            atol=tol, rtol=tol)
        assert (np.asarray(lg).argmax(-1) ==
                np.asarray(logits_p).argmax(-1)).all()


@pytest.mark.parametrize("arch", ARCHS)
def test_param_count_matches_init(arch):
    cfg = reduce_config(get_config(arch))
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    n_init = sum(l.size for l in jax.tree.leaves(params))
    assert n_init == cfg.param_count(), \
        f"{arch}: analytic {cfg.param_count()} != init {n_init}"


def test_cells_cover_40():
    """Four dry-run shapes an arch: 40 for the ten assigned archs and 4 for
    granite-4.0-h-small, whose long-context cell runs (a hybrid, like
    jamba); the seven pure full-attention archs skip theirs."""
    total = sum(len(cells(a)) for a in ARCHS)
    assert total == 4 * len(ARCHS) == 44
    runs = sum(1 for a in ARCHS for _, s in cells(a) if s == "RUN")
    skips = total - runs
    assert runs == 37 and skips == 7
