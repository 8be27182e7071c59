"""The float32 references against the program, at small sizes on the CPU.

Every configuration of ``BENCHMARK.json``, at its tiny stand-in.  Both
sides compute in float32 here (the program's ``dtype`` set to float32), so
they agree to float32 rounding: the reference is the same model, written
independently.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import generator, harness, weights
from bench.reference import corpus, train as ref_train
from bench.tests import tiny

jax.config.update("jax_default_matmul_precision", "highest")
CONFIGS = [c["name"] for c in
           harness.load_json(harness.ROOT / "BENCHMARK.json")["configs"]]


def _setup(name):
    conf = tiny.config(name)
    cfg = dataclasses.replace(harness.program_config(conf), dtype="float32",
                              remat="none")
    from repro.models import transformer as T
    params = weights.maker(T.abstract_params(cfg))(weights.seed_key(7))
    tok, tgt = corpus.rows(7, cfg.vocab_size, 0, 2, 32)
    return conf, cfg, params, tok, tgt


@pytest.mark.parametrize("name", CONFIGS)
def test_loss_and_grads_match_program(name):
    from repro.models import model as M
    conf, cfg, params, tok, tgt = _setup(name)
    batch = {"tokens": jnp.asarray(tok), "targets": jnp.asarray(tgt),
             "loss_mask": jnp.ones(tok.shape, jnp.float32)}
    (loss, _), g_prog = jax.value_and_grad(
        lambda p: M.loss_fn(cfg, p, batch), has_aux=True)(params)
    loss_ref, g_ref = ref_train.loss_and_grads(
        conf["reference"], generator.ref_model(conf), weights.flat(params),
        tok, tgt, rows=1)
    assert abs(float(loss) - loss_ref) < 1e-4 * abs(loss_ref)
    g_prog = weights.flat(g_prog)
    for k, g in g_ref.items():
        np.testing.assert_allclose(np.asarray(g_prog[k]), np.asarray(g),
                                   rtol=2e-3, atol=2e-6, err_msg=k)


def test_half_rows_change_the_loss():
    conf, cfg, params, tok, tgt = _setup("smollm-360m")
    model = generator.ref_model(conf)
    full, _ = ref_train.loss_and_grads("transformer", model,
                                       weights.flat(params), tok, tgt)
    half, _ = ref_train.loss_and_grads("transformer", model,
                                       weights.flat(params), tok, tgt,
                                       half=True)
    assert full != half


def test_seed_key_takes_seeds_wider_than_32_bits():
    a = jax.random.key_data(weights.seed_key(2 ** 40 + 3))
    b = jax.random.key_data(weights.seed_key(3))
    assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_corpus_copy_matches_program_loader():
    from repro.data.pipeline import DataConfig, ShardedLoader
    loader = ShardedLoader(DataConfig(vocab_size=257, seq_len=16,
                                      global_batch=3, seed=2 ** 35 + 1))
    try:
        for i in range(2):
            b = next(loader)
            tok, tgt = corpus.rows(2 ** 35 + 1, 257, i * 3 * 17, 3, 16)
            np.testing.assert_array_equal(b["tokens"], tok)
            np.testing.assert_array_equal(b["targets"], tgt)
    finally:
        loader.close()
