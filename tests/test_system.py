"""End-to-end system tests: workflow-managed training with checkpoint
restart; journal replay; sharded-model numerics on a multi-device mesh
(subprocess — device count is fixed at jax init, so the 8-device check runs
isolated)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]


def test_train_driver_end_to_end(tmp_path):
    from repro.launch.train import main
    losses = main(["--arch", "smollm-360m", "--reduced", "--steps", "20",
                   "--segment", "5", "--batch", "4", "--seq", "64",
                   "--ckpt-dir", str(tmp_path / "ck"),
                   "--ckpt-every", "10", "--eval-every", "20"])
    assert len(losses) == 4
    assert losses[-1] < losses[0] + 0.2      # moving in the right direction
    # restart picks up from the checkpoint
    losses2 = main(["--arch", "smollm-360m", "--reduced", "--steps", "30",
                    "--segment", "5", "--batch", "4", "--seq", "64",
                    "--ckpt-dir", str(tmp_path / "ck"),
                    "--ckpt-every", "10", "--eval-every", "30"])
    assert len(losses2) == 2                 # only steps 20->30 ran


def test_compile_cache_dir(monkeypatch):
    """An entry point keeps JAX's cache where JAX_COMPILATION_CACHE_DIR
    says, and otherwise at the fixed <repo>/.jax_cache; either way the key
    holds the programs' op names (named scopes) and no source locations,
    so a program compiled from other code is not loaded in their place and
    a checkout elsewhere loads the same programs."""
    from repro.launch.compile_cache import enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    key_meta = jax.config.jax_compilation_cache_include_metadata_in_key
    frames = jax.config.jax_traceback_in_locations_limit
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          False)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "cache-from-env")
        assert enable_compile_cache() == "cache-from-env"
        assert jax.config.jax_compilation_cache_dir is None   # left alone
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          False)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        assert jax.config.jax_traceback_in_locations_limit == 0
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          key_meta)
        jax.config.update("jax_traceback_in_locations_limit", frames)


def test_serve_driver_end_to_end():
    from repro.launch.serve import main
    outputs = main(["--arch", "smollm-360m", "--reduced", "--requests", "6",
                    "--batch-slots", "3", "--max-new", "6"])
    assert len(outputs) == 6
    assert all(len(v) >= 1 for v in outputs.values())


def test_store_journal_replay(tmp_path):
    from repro.core import StateStore, TaskRecord, TaskState
    j = tmp_path / "journal.jsonl"
    s1 = StateStore(str(j))
    t = TaskRecord(uid="task.x", kind="python")
    t.result = {"answer": 42}
    t.state = TaskState.DONE
    s1.record(t, workflow_key="wf/app:0")
    s1.close()
    s2 = StateStore(str(j))
    found, result = s2.completed_result("wf/app:0")
    assert found and result == {"answer": 42}
    s2.close()


def test_dfk_replay_skips_done_tasks(tmp_path):
    from repro.core import (DataFlowKernel, PilotDescription, RPEXExecutor,
                            python_app)
    journal = str(tmp_path / "wf.jsonl")
    calls = []

    @python_app
    def work(x):
        calls.append(x)
        return x * 10

    rp1 = RPEXExecutor(PilotDescription(n_slots=4, journal=journal))
    with DataFlowKernel(executors={"rpex": rp1}, run_id="r1"):
        assert work(3).result() == 30
    rp1.shutdown()
    assert calls == [3]
    # "restart": same run_id + journal -> replay, no re-execution
    rp2 = RPEXExecutor(PilotDescription(n_slots=4, journal=journal))
    with DataFlowKernel(executors={"rpex": rp2}, run_id="r1"):
        assert work(3).result() == 30
    rp2.shutdown()
    assert calls == [3]


SHARDED_CHECK = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config, reduce_config
from repro.models import model as M
from repro.models import transformer as T
from repro.optim import AdamW
from repro.sharding.partition import PartitionRules, ShardCtx, make_mesh

# sharded-vs-local train step parity on a reduced MoE config
cfg = reduce_config(get_config("qwen3-moe-235b-a22b"), num_layers=2)
mesh = make_mesh((2, 4))
rules = PartitionRules()
params = T.init_params(cfg, jax.random.PRNGKey(0))
B, S = 4, 16
key = jax.random.PRNGKey(1)
batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
         "targets": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
         "loss_mask": jnp.ones((B, S))}
loss_local, _ = M.loss_fn(cfg, params, batch)

pspecs = T.param_pspecs(cfg, mesh, rules)
shard = lambda t, s: jax.device_put(t, jax.NamedSharding(mesh, s))
params_sh = jax.tree.map(shard, params, pspecs)
sctx = ShardCtx(mesh, rules)
with mesh:
    loss_sh, _ = jax.jit(lambda p, b: M.loss_fn(cfg, p, b, sctx))(params_sh, batch)
err = abs(float(loss_local) - float(loss_sh))
assert err < 5e-2, f"sharded loss diverges: {float(loss_local)} vs {float(loss_sh)}"
print("SHARDED-PARITY-OK", float(loss_local), float(loss_sh))

# sharded attention strategies + decode (exercised via gemma2 family: window+softcap)
cfg2 = reduce_config(get_config("gemma2-9b"), num_layers=2)
params2 = T.init_params(cfg2, jax.random.PRNGKey(2))
batch2 = {"tokens": jax.random.randint(key, (B, S), 0, cfg2.vocab_size),
          "targets": jax.random.randint(key, (B, S), 0, cfg2.vocab_size),
          "loss_mask": jnp.ones((B, S))}
l_loc, _ = M.loss_fn(cfg2, params2, batch2)
p2sh = jax.tree.map(shard, params2, T.param_pspecs(cfg2, mesh, rules))
with mesh:
    l_sh, _ = jax.jit(lambda p, b: M.loss_fn(cfg2, p, b, ShardCtx(mesh, rules)))(p2sh, batch2)
assert abs(float(l_loc) - float(l_sh)) < 5e-2, (float(l_loc), float(l_sh))
print("GEMMA-SHARDED-OK")
"""


@pytest.mark.slow
def test_sharded_model_parity_subprocess():
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SHARDED_CHECK], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARDED-PARITY-OK" in out.stdout
    assert "GEMMA-SHARDED-OK" in out.stdout


def test_dryrun_artifacts_complete():
    """The multi-pod dry-run must have produced all 40 cells x 2 meshes."""
    base = REPO / "benchmarks" / "artifacts" / "dryrun"
    if not base.exists():
        pytest.skip("dry-run artifacts not generated yet")
    for mesh in ("pod16x16", "pod2x16x16"):
        files = list((base / mesh).glob("*.json"))
        assert len(files) == 40, f"{mesh}: {len(files)} cells"
        for f in files:
            a = json.loads(f.read_text())
            assert a["status"] in ("ok", "SKIP(full-attn)"), \
                f"{f.name}: {a.get('status')} {a.get('error', '')[:200]}"
            if a["status"] == "ok":
                assert a["cost"]["flops_per_device"] > 0
                assert a["peak_bytes_per_device"] > 0
