"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles,
plus hypothesis property tests on the SSD recurrence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
import hypothesis.strategies as st
from hypothesis import given, settings

from repro.kernels import ops
from repro.kernels.ref import (attention_reference, ssd_reference,
                               ssd_sequential)
from repro.models.attention import blockwise_attention

KEY = jax.random.PRNGKey(42)


@pytest.mark.parametrize("B,Sq,Hq,Hkv,D", [
    (1, 32, 2, 2, 16),
    (2, 64, 4, 2, 32),
    (1, 100, 8, 8, 64),      # ragged seq (padding path)
    (2, 96, 6, 3, 16),
    (1, 128, 16, 4, 64),     # deep GQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_kernel_sweep(B, Sq, Hq, Hkv, D, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, Hq, D), dtype)
    k = jax.random.normal(ks[1], (B, Sq, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, Sq, Hkv, D), dtype)
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-5
    for window, cap in [(0, 0.0), (13, 0.0), (0, 30.0), (13, 30.0)]:
        got = ops.flash_attention(q, k, v, causal=True, window=window,
                                  attn_softcap=cap, block_q=32, block_k=32)
        want = attention_reference(q, k, v, causal=True, window=window,
                                   attn_softcap=cap)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=tol, rtol=tol)


@pytest.mark.parametrize("B,S,Hq,Hkv,D,window,cap", [
    (2, 128, 6, 2, 64, 0, 0.0),       # grouped heads, causal
    (1, 200, 4, 2, 64, 37, 20.0),     # window + soft cap; S padded to 256
])
def test_splash_attention_matches_reference(B, S, Hq, Hkv, D, window, cap):
    """The fused kernel (interpret mode here): output and dq/dk/dv."""
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, S, Hq, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    do = jax.random.normal(ks[3], (B, S, Hq, D))
    results = []
    for attn in (lambda q, k, v: ops.splash_attention(
                     q, k, v, window=window, attn_softcap=cap),
                 lambda q, k, v: attention_reference(
                     q, k, v, causal=True, window=window, attn_softcap=cap)):
        out, vjp = jax.vjp(attn, q, k, v)
        results.append((out,) + vjp(do))
    for name, got, want in zip(("out", "dq", "dk", "dv"), *results):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5, err_msg=name)


@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (1, 32, 2, 8, 4, 8),
    (2, 64, 4, 16, 8, 16),
    (1, 128, 2, 32, 16, 32),
    (2, 48, 3, 8, 8, 16),    # chunk not dividing heads evenly is fine
])
def test_ssd_kernel_sweep(B, S, H, P, N, chunk):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    B_ = jax.random.normal(ks[3], (B, S, N))
    C_ = jax.random.normal(ks[4], (B, S, N))
    y1, h1 = ops.ssd(x, dt, A, B_, C_, chunk)
    y2, h2 = ssd_sequential(x, dt, A, B_, C_)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               atol=5e-4, rtol=5e-4)


def test_flash_vjp_matches_reference_grads():
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (2, 40, 6, 16))
    k = jax.random.normal(ks[1], (2, 40, 3, 16))
    v = jax.random.normal(ks[2], (2, 40, 3, 16))
    do = jax.random.normal(ks[3], (2, 40, 6, 16))
    zero = jnp.zeros((), jnp.int32)
    for window, cap in [(0, 0.0), (11, 20.0)]:
        g1 = jax.grad(lambda q, k, v: (blockwise_attention(
            q, k, v, zero, True, window, cap, 16, 16) * do).sum(),
            argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(lambda q, k, v: (attention_reference(
            q, k, v, causal=True, window=window, attn_softcap=cap)
            * do).sum(), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5, rtol=5e-5)


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 3), st.integers(1, 4), st.integers(0, 1000))
def test_ssd_chunking_invariance(b, h, seed):
    """Chunked == sequential for any chunk size dividing S (property)."""
    S, P, N = 32, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (b, S, h, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, S, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B_ = jax.random.normal(ks[3], (b, S, N))
    C_ = jax.random.normal(ks[4], (b, S, N))
    y_seq, h_seq = ssd_sequential(x, dt, A, B_, C_)
    for chunk in (4, 8, 16, 32):
        y_c, h_c = ssd_reference(x, dt, A, B_, C_, chunk=chunk)
        np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_seq),
                                   atol=1e-3, rtol=1e-3)
        np.testing.assert_allclose(np.asarray(h_c), np.asarray(h_seq),
                                   atol=1e-3, rtol=1e-3)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 10_000))
def test_flash_blockwise_invariance(seed):
    """blockwise == reference for random block sizes (property)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (1, 24, 4, 8))
    k = jax.random.normal(ks[1], (1, 24, 2, 8))
    v = jax.random.normal(ks[2], (1, 24, 2, 8))
    want = attention_reference(q, k, v, causal=True)
    rng = np.random.default_rng(seed)
    bq, bk = int(rng.integers(1, 25)), int(rng.integers(1, 25))
    got = blockwise_attention(q, k, v, jnp.zeros((), jnp.int32), True, 0,
                              0.0, bk, bq)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=3e-5)
