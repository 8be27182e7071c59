"""Which attention path each call takes, and the trace-time count of it.

On a TPU, attention over a whole sequence runs the fused kernel wherever q
starts at the static offset 0 under a causal mask: unsharded, and in the
``local``, ``kv_heads`` and ``q_heads`` strategies of
``sharded_flash_attention``; the ``seq`` strategy's q chunks keep
``blockwise_attention``.  The TPU is stood in for by patching the backend
check, and those calls are only traced (``jax.eval_shape``, abstract
meshes), never lowered.  On the CPU every call takes the jnp path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, AxisType

from repro.configs import ARCHS, get_config, reduce_config
from repro.core import tracing
from repro.kernels import ops
from repro.kernels.ref import attention_reference
from repro.models import attention as A
from repro.models import model as M
from repro.models import transformer as T

STRATEGIES = ("local", "kv_heads", "q_heads", "seq")
EXPECTED = {"local": "attention.kernel", "kv_heads": "attention.kernel",
            "q_heads": "attention.kernel", "seq": "attention.jnp.q_offset"}
SEQ = 1536          # divisible by every model axis the seq strategy needs


def _delta(before):
    return {k: v - before.get(k, 0)
            for k, v in tracing.counts("attention.").items()
            if v != before.get(k, 0)}


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(ops, "on_tpu", lambda: True)


def test_attention_path_reads_backend_offset_and_mask(monkeypatch):
    assert A.attention_path(0) == ("jnp", "backend")
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    assert A.attention_path(0) == ("kernel", "")
    assert A.attention_path(jnp.zeros((), jnp.int32)) == ("jnp", "q_offset")
    assert A.attention_path(0, causal=False) == ("jnp", "mask")


@pytest.mark.parametrize("arch", [a for a in ARCHS
                                  if get_config(a).num_heads])
def test_every_arch_and_strategy_takes_the_expected_path_on_tpu(arch, on_tpu):
    cfg = get_config(arch)
    Hq, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jax.ShapeDtypeStruct((2, SEQ, Hq, D), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((2, SEQ, Hkv, D), jnp.bfloat16)
    reached = set()
    for strategy in STRATEGIES:
        m = next((m for m in range(1, 2 * Hq + 1)
                  if A.tp_strategy(SEQ, Hq, Hkv, m) == strategy), None)
        if m is None:
            continue
        reached.add(strategy)
        mesh = AbstractMesh((1, m), ("data", "model"),
                            axis_types=(AxisType.Auto,) * 2)
        for window in sorted({0, cfg.sliding_window}):
            before = tracing.counts("attention.")
            out = jax.eval_shape(
                lambda q, k, v: A.sharded_flash_attention(
                    mesh, q, k, v, window=window,
                    attn_softcap=cfg.attn_softcap), q, kv, kv)
            assert out.shape == q.shape
            assert _delta(before) == {EXPECTED[strategy]: 1}, (
                arch, strategy, m, window)
    want = {"local", "kv_heads", "seq"} | ({"q_heads"} if Hq > Hkv else set())
    assert reached == want, arch


def test_non_causal_call_keeps_the_jnp_path_on_tpu(on_tpu):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (1, 24, 4, 16))
    k = jax.random.normal(ks[1], (1, 24, 2, 16))
    v = jax.random.normal(ks[2], (1, 24, 2, 16))
    before = tracing.counts("attention.")
    got = A.flash_attention(q, k, v, causal=False)
    assert _delta(before) == {"attention.jnp.mask": 1}
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(attention_reference(q, k, v, causal=False)),
        atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("arch", ["smollm-360m", "gemma2-9b"])
def test_every_call_on_cpu_counts_jnp_backend(arch):
    cfg = reduce_config(get_config(arch))
    key = jax.random.PRNGKey(0)
    params = T.init_params(cfg, key)
    tok = jax.random.randint(key, (2, 16), 0, cfg.vocab_size)
    before = tracing.counts("attention.")
    M.loss_fn(cfg, params, {"tokens": tok, "targets": tok,
                            "loss_mask": jnp.ones((2, 16))})
    delta = _delta(before)
    assert set(delta) == {"attention.jnp.backend"}
    assert delta["attention.jnp.backend"] >= 1
