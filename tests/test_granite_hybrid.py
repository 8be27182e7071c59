"""Granite-4.0-H-Small's layers against the plain float32 reference.

The program (``repro.models``) runs a tiny, float32 copy of the
``granite-4.0-h-small`` registry entry (one whole 10-layer period: five
Mamba2 layers, NoPE attention at index 5, four Mamba2; an expert layer in
each) on seeded random weights from ``bench.weights``, and is compared with
``bench/reference/granite_hybrid.py`` on the same weights: the whole hybrid
forward's per-token losses, one Mamba2 layer with its conv bias and gated
norm, and the NoPE attention layer.  The expert layer has its own tests
(``test_moe.py``).  Also here: the models that were there before keep
their layer programs and parameter trees, and the hybrid's trace carries
its layer scopes and the expert layer's count.
"""
import dataclasses
import hashlib
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))  # bench/

from bench import reference, weights  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import tracing  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.models import attention as A  # noqa: E402
from repro.models import mamba2  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.sharding.partition import NULL_CTX  # noqa: E402

REF = reference.model("granite_hybrid")
# Float32 against float32: the two differ only in the order of their sums
# (chunked against quadratic SSD, blockwise against dense softmax, grouped
# against dense expert matmuls).  Per-token losses of about 4.6 read 5e-7
# apart after ten layers, so 2e-5 leaves 40x; the program in bfloat16 reads
# 2e-4 apart and fails it (checked below).  A single layer's output reads
# 1.5e-7 of its largest magnitude apart; 1e-5 of it leaves 70x.
NLL_TOL = 2e-5
LAYER_TOL = 1e-5


def gap(got, want) -> float:
    """Largest difference, over the reference output's largest magnitude."""
    want = np.asarray(want)
    return float(np.max(np.abs(np.asarray(got) - want)) / np.max(np.abs(want)))


def tiny(dtype="float32", **kw):
    return dataclasses.replace(
        get_config("granite-4.0-h-small"), num_layers=10, d_model=64,
        num_heads=4, num_kv_heads=2, head_dim=16, attn_scale=1 / 16,
        d_inner=128, ssm_head_dim=32, ssm_state=16, ssm_chunk=8,
        num_experts=16, moe_held=4, moe_first=4, num_experts_per_tok=3,
        d_ff=32, shared_expert_ff=64, vocab_size=97, dtype=dtype, **kw)


def ref_cfg(cfg) -> dict:
    return {"num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "attention_multiplier": cfg.attn_scale,
            "mamba_n_heads": cfg.ssm_heads, "mamba_d_head": cfg.ssm_head_dim,
            "mamba_d_state": cfg.ssm_state, "first_expert": cfg.moe_first,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "rms_norm_eps": cfg.norm_eps,
            "embedding_multiplier": cfg.embedding_multiplier,
            "residual_multiplier": cfg.residual_multiplier,
            "logits_scaling": cfg.logits_scaling}


def make(cfg, seed=7):
    return weights.maker(T.abstract_params(cfg))(weights.seed_key(seed))


def flat32(params) -> dict:
    return {k: v.astype(jnp.float32) for k, v in weights.flat(params).items()}


def program_nll(cfg, params, tok, tgt):
    x = M.embed_inputs(cfg, params, {"tokens": tok})
    h, _, _ = T.forward(cfg, params, x, mode="train")
    logits = M.lm_logits(cfg, params, h).astype(jnp.float32)
    gold = jnp.take_along_axis(logits, tgt[..., None], -1)[..., 0]
    return jax.nn.logsumexp(logits, axis=-1) - gold


def _tokens(cfg, B=2, S=24):
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    return (jax.random.randint(k1, (B, S), 0, cfg.vocab_size),
            jax.random.randint(k2, (B, S), 0, cfg.vocab_size))


def _hidden(cfg, seed=11, B=2, S=24):
    return jax.random.normal(jax.random.PRNGKey(seed), (B, S, cfg.d_model))


def test_layer_program_is_one_period_with_attention_at_five():
    cfg = tiny()
    assert cfg.layer_types == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert T.program_period(cfg) == 10
    assert {f for _, f in T.layer_program(cfg)} == {"moe"}
    full = get_config("granite-4.0-h-small")
    assert [i for i, t in enumerate(full.layer_types)
            if t == "attention"] == [5, 15, 25, 35]


def test_hybrid_forward_matches_reference():
    cfg = tiny()
    params = make(cfg)
    tok, tgt = _tokens(cfg)
    got = np.asarray(program_nll(cfg, params, tok, tgt))
    want = np.asarray(REF.token_nll(flat32(params), tok, tgt, ref_cfg(cfg)))
    np.testing.assert_allclose(got, want, atol=NLL_TOL, rtol=0)
    # the tolerance has teeth: the same weights in bfloat16 miss it
    cfg16 = dataclasses.replace(cfg, dtype="bfloat16")
    p16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    bf16 = np.asarray(program_nll(cfg16, p16, tok, tgt))
    assert np.max(np.abs(bf16 - want)) > 5 * NLL_TOL


def test_mamba_layer_with_conv_bias_and_gated_norm_matches_reference():
    cfg = tiny()
    params = make(cfg)
    w = jax.tree.map(lambda a: a[0], params["layers"][0])
    assert {"conv_b", "norm"} <= set(w["mixer"])
    h = _hidden(cfg)
    got, _ = mamba2.mamba_layer(cfg, w["mixer"], h, sctx=NULL_CTX)
    want = REF.mamba_mixer(h, REF.layers(flat32(params))[0],
                           ref_cfg(cfg), "f32")
    assert gap(got, want) < LAYER_TOL
    # each option changes the output: without it the layer misses
    for off in (dict(mamba_conv_bias=False), dict(mamba_gated_norm=False)):
        o, _ = mamba2.mamba_layer(dataclasses.replace(cfg, **off),
                                  w["mixer"], h, sctx=NULL_CTX)
        assert gap(o, want) > 100 * LAYER_TOL, off


def test_nope_attention_layer_matches_reference():
    cfg = tiny()
    params = make(cfg)
    w = jax.tree.map(lambda a: a[0], params["layers"][5])
    assert "wq" in w["mixer"]
    h = _hidden(cfg)
    got, _ = A.attention_layer(cfg, w["mixer"], h, local=False, sctx=NULL_CTX)
    want = REF.attention(h, REF.layers(flat32(params))[5],
                         ref_cfg(cfg), "f32")
    assert gap(got, want) < LAYER_TOL
    # with rotary positions or the default 1/sqrt(D) scale it would not match
    for other in (dict(position_embedding="rope"), dict(attn_scale=0.0)):
        o, _ = A.attention_layer(dataclasses.replace(cfg, **other),
                                 w["mixer"], h, local=False, sctx=NULL_CTX)
        assert gap(o, want) > 100 * LAYER_TOL, other


def test_nope_attention_takes_the_fused_kernel_on_tpu(monkeypatch):
    """Traced only (abstract shapes), with the TPU stood in for."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    cfg = get_config("granite-4.0-h-small")
    w = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                     T.abstract_params(cfg)["layers"][5])
    h = jax.ShapeDtypeStruct((2, 512, cfg.d_model), jnp.bfloat16)
    before = tracing.counts("attention.")
    out, _ = jax.eval_shape(lambda w, h: A.attention_layer(
        cfg, w, h, local=False, sctx=NULL_CTX), w["mixer"], h)
    assert out.shape == h.shape
    after = tracing.counts("attention.")
    assert {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)} == {"attention.kernel": 1}


def test_forward_only_pallas_kernel_refuses_a_score_scale():
    """``use_pallas``'s kernel has no scale option: it must refuse, not
    silently score at D ** -0.5."""
    cfg = tiny()
    w = jax.tree.map(lambda a: a[0], make(cfg)["layers"][5])
    with pytest.raises(ValueError, match="attn_scale"):
        A.attention_layer(cfg, w["mixer"], _hidden(cfg), local=False,
                          sctx=NULL_CTX, use_pallas=True)


def test_forward_carries_the_layer_scopes_and_counts_the_expert_layer():
    cfg = tiny()
    params = make(cfg)
    tok, tgt = _tokens(cfg)
    before = tracing.counts("moe.").get("moe.dispatch.ragged", 0)
    hlo = jax.jit(lambda p, t, g: program_nll(cfg, p, t, g)).lower(
        params, tok, tgt).compile().as_text()
    assert tracing.counts("moe.")["moe.dispatch.ragged"] - before == 10
    scopes = set()
    for name in re.findall(r'op_name="([^"]*)"', hlo):
        scopes.update(re.findall(
            r"(?:^|[/(])(embed|attention|ssd|mlp|loss|moe|experts)"
            r"(?=$|[/):])", name))
    assert scopes == {"embed", "attention", "ssd", "mlp", "loss", "moe",
                      "experts"}


# sha256 of the (path, shape, dtype) list of each registry model's abstract
# parameter tree, as it was before the hybrid's options came in
KEPT = {"smollm-360m": ("025c23f34722791f", [("attn", "dense")] * 32),
        "mamba2-1.3b": ("15a514cab6257d10", [("mamba", "none")] * 48),
        "jamba-1.5-large-398b": ("d0e0f526c5a9dfb8", [
            ("attn" if i % 8 == 7 else "mamba", "moe" if i % 2 else "dense")
            for i in range(72)])}


@pytest.mark.parametrize("arch", sorted(KEPT))
def test_earlier_models_keep_their_programs_and_parameter_trees(arch):
    cfg = get_config(arch)
    digest, program = KEPT[arch]
    assert T.layer_program(cfg) == program
    leaves = jax.tree_util.tree_flatten_with_path(T.abstract_params(cfg))[0]
    text = ";".join(f"{jax.tree_util.keystr(p)}:{x.shape}:{x.dtype}"
                    for p, x in leaves)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest
