"""Compiles for a described TPU v5e chip, no chip attached.

The TPU compiler refuses what interpret mode accepts: block shapes off the
(8, 128) tiling, kernels over their fast-memory budget, programs larger
than the device.  These compiles run the main path's kernels and its
full-width train step through it at real sizes.  Nothing runs, so they say
nothing about results or times.  The models pick the fused attention
kernel from the backend, which is the CPU here: a test that compiles the
kernel's path stands the TPU in by patching ``ops.on_tpu``.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.ssd import ssd_chunk_kernel
from repro.launch.train import build_state
from repro.models import model as M

V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one (it warns): keep the cache off here
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def test_flash_attention_compiles_for_v5e_at_smollm_widths(one_chip):
    q = jax.ShapeDtypeStruct((8, 1024, 15, 64), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((8, 1024, 5, 64), jnp.bfloat16,
                              sharding=one_chip)
    compiled = jax.jit(
        lambda q, k, v: flash_attention_fwd(q, k, v, interpret=False)
    ).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_splash_attention_compiles_for_v5e_at_smollm_widths(one_chip):
    """The models' kernel, forward and backward, at the train cell's
    shapes: batch 8 x 2048, 15 query and 5 key-value heads of 64."""
    q = jax.ShapeDtypeStruct((8, 2048, 15, 64), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((8, 2048, 5, 64), jnp.bfloat16,
                              sharding=one_chip)

    def fwd_bwd(q, k, v):
        out, vjp = jax.vjp(lambda *a: ops.splash_attention(
            *a, interpret=False), q, k, v)
        return vjp(out)

    compiled = jax.jit(fwd_bwd).lower(q, kv, kv).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3   # fwd, dq, dkv


def test_ssd_kernel_compiles_for_v5e_at_mamba2_widths(one_chip):
    cfg = get_config("mamba2-1.3b")
    B, S, H, P, N = 2, 2048, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    assert (H, P, N, cfg.ssm_chunk) == (64, 64, 128, 256)
    f32 = jnp.float32
    args = _on(one_chip, (
        jax.ShapeDtypeStruct((B, S, H, P), f32),
        jax.ShapeDtypeStruct((B, S, H), f32),
        jax.ShapeDtypeStruct((H,), f32),
        jax.ShapeDtypeStruct((B, S, N), f32),
        jax.ShapeDtypeStruct((B, S, N), f32)))
    compiled = jax.jit(lambda *a: ssd_chunk_kernel(
        *a, chunk=cfg.ssm_chunk, interpret=False)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _smollm_train_step(one_chip, batch: int, seq: int) -> tuple:
    """(argument and temporary bytes, Pallas kernel calls) of the full-width
    SmolLM-360M step (bf16 params, f32 AdamW) compiled for one chip."""
    cfg = get_config("smollm-360m")
    made = {}

    def state():
        params, made["opt"], opt_state = build_state(cfg, None, None)
        return params, opt_state

    params, opt_state = _on(one_chip, jax.eval_shape(state))
    tok = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    batch = _on(one_chip, {"tokens": tok, "targets": tok,
                           "loss_mask": jax.ShapeDtypeStruct((batch, seq),
                                                             jnp.float32)})
    step = M.make_train_step(cfg, made["opt"])
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        params, opt_state, batch).compile()
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.temp_size_in_bytes,
            compiled.as_text().count("tpu_custom_call"))


def test_smollm_train_step_fits_one_v5e(one_chip):
    """``launch/train.py``'s full-width step (no --reduced, batch 8 x seq
    1024, bf16 params, f32 AdamW) compiles for one chip and fits its HBM."""
    used, _ = _smollm_train_step(one_chip, 8, 1024)
    assert used < V5E_HBM_BYTES, used


def test_smollm_train_step_with_kernel_fits_one_v5e(one_chip, monkeypatch):
    """The benchmark's step, 8 x 2048, on the fused attention kernel fits
    the chip and needs no more than the same step on the jnp path."""
    jnp_path, jnp_kernels = _smollm_train_step(one_chip, 8, 2048)
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    kernel_path, kernels = _smollm_train_step(one_chip, 8, 2048)
    assert (jnp_kernels, kernels >= 3) == (0, True), (jnp_kernels, kernels)
    assert kernel_path < V5E_HBM_BYTES, kernel_path
    assert kernel_path <= jnp_path, (kernel_path, jnp_path)
