"""Smoke run of the main path on a TPU: proof that the system starts there.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the multi-chip path, on four chips

One chip: the Pallas kernels compiled at real widths and compared with the
float32 references of ``repro.kernels.ref`` (the models' fused attention
kernel forward and backward), then SmolLM-360M at its full
published width trained through the workflow path (DFK -> RPEXExecutor ->
pilot -> agent -> SPMDFunctionExecutor -> jitted step) in two segments with
a checkpoint and an eval, then resumed from that checkpoint for one more
segment.  ``--four-chips`` runs only the multi-chip path and what it is
compared with: a 2x2-sharded first step against the same step on one
chip, and four one-slot SPMD tasks that must land on four distinct chips.

The last line of stdout is a JSON object ``{"ok": true, "device": ...}``,
printed only when every phase passed.  Without a TPU the script exits
non-zero before any phase.  Times and memory printed on the way are
informational.
"""
from __future__ import annotations

import argparse
import json
import math
import shutil
import sys
import threading
import time
from functools import partial
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SCRATCH = ROOT / ".smoke"           # checkpoints; removed when the run ends

# SmolLM-360M driver run: full width, depth and widths as published
TRAIN = ["--arch", "smollm-360m", "--batch", "8", "--seq", "1024",
         "--segment", "2"]
FLASH_BATCH_SEQ = (8, 1024)    # the train run's attention shapes
SSD_BATCH_SEQ = (2, 2048)
FLASH_TOL = 3e-2     # bf16 inputs and output, as the interpret-mode tests
SSD_TOL = 2e-2       # of max |reference|: f32 MXU passes may round to bf16
PARITY_TOL = 5e-2    # sharded vs one-chip loss, as the CPU parity test


def check(ok: bool, what: str):
    if not ok:
        raise SystemExit(f"[smoke] FAILED: {what}")


def say(msg: str):
    print(f"[smoke] {msg}", flush=True)


def kernels_phase():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.kernels import ops, ref

    def max_err(got, want):
        return float(np.max(np.abs(np.asarray(got, np.float32)
                                   - np.asarray(want, np.float32))))

    def compiled_kernel(fn, *args):
        check("tpu_custom_call" in fn.lower(*args).as_text(),
              f"{fn.__name__} runs as a compiled TPU kernel")
        return fn(*args)

    smol = get_config("smollm-360m")
    ks = jax.random.split(jax.random.PRNGKey(0), 9)
    (B, S), D = FLASH_BATCH_SEQ, smol.head_dim
    q = jax.random.normal(ks[0], (B, S, smol.num_heads, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, smol.num_kv_heads, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, smol.num_kv_heads, D), jnp.bfloat16)
    got = compiled_kernel(jax.jit(ops.flash_attention), q, k, v)
    with jax.default_matmul_precision("highest"):
        f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
        want = jax.jit(ref.attention_reference)(f32(q), f32(k), f32(v))
    err = max_err(got, want)
    say(f"flash_attention q{tuple(q.shape)} kv{tuple(k.shape)} bf16: "
        f"max abs err {err:.3e} (tolerance {FLASH_TOL})")
    check(err <= FLASH_TOL, "flash_attention disagrees with the reference")

    do = jax.random.normal(ks[8], q.shape, jnp.bfloat16)

    def fwd_bwd(attn, *a):
        out, vjp = jax.vjp(attn, *a[:3])
        return (out,) + vjp(a[3])

    got = compiled_kernel(jax.jit(partial(fwd_bwd, ops.splash_attention)),
                          q, k, v, do)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(partial(fwd_bwd, ref.attention_reference))(
            f32(q), f32(k), f32(v), f32(do))
    for name, g, w in zip(("out", "dq", "dk", "dv"), got, want):
        scale = float(jnp.max(jnp.abs(w)))
        err = max_err(g, w)
        say(f"splash_attention {name}: max abs err {err:.3e} (tolerance "
            f"{FLASH_TOL} x max|ref| = {FLASH_TOL * scale:.3e})")
        check(err <= FLASH_TOL * scale,
              f"splash_attention {name} disagrees with the reference")

    mamba = get_config("mamba2-1.3b")
    H, P, N, Q = (mamba.ssm_heads, mamba.ssm_head_dim, mamba.ssm_state,
                  mamba.ssm_chunk)
    B, S = SSD_BATCH_SEQ
    bf16_grid = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)  # noqa
    x = bf16_grid(jax.random.normal(ks[3], (B, S, H, P)))
    dt = jax.nn.softplus(jax.random.normal(ks[4], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[5], (H,)) * 0.5)
    Bm = bf16_grid(jax.random.normal(ks[6], (B, S, N)))
    Cm = bf16_grid(jax.random.normal(ks[7], (B, S, N)))
    y, h = compiled_kernel(jax.jit(ops.ssd, static_argnums=5),
                           x, dt, A, Bm, Cm, Q)
    with jax.default_matmul_precision("highest"):
        y_ref, h_ref = jax.jit(
            lambda *a: ref.ssd_reference(*a, chunk=Q))(x, dt, A, Bm, Cm)
    for name, g, w in (("y", y, y_ref), ("final state", h, h_ref)):
        scale = float(jnp.max(jnp.abs(w)))
        err = max_err(g, w)
        say(f"ssd x{tuple(x.shape)} N={N} chunk={Q} {name}: max abs err "
            f"{err:.3e} (tolerance {SSD_TOL} x max|ref| = "
            f"{SSD_TOL * scale:.3e})")
        check(math.isfinite(err) and err <= SSD_TOL * scale,
              f"ssd {name} disagrees with the reference")


def train_phase():
    from repro.launch import train

    ckpt = str(SCRATCH / "ckpt")
    common = TRAIN + ["--ckpt-dir", ckpt, "--ckpt-every", "4",
                      "--eval-every", "4"]
    t = time.time()
    losses = train.main(common + ["--steps", "4", "--no-resume"])
    say(f"train 4 steps in 2 segments: losses {losses} "
        f"({time.time() - t:.1f}s)")
    check(len(losses) == 2, "one loss per segment")
    check(all(math.isfinite(l) for l in losses), "finite losses")
    t = time.time()
    resumed = train.main(common + ["--steps", "6"])
    say(f"resume to step 6: losses {resumed} ({time.time() - t:.1f}s)")
    check(len(resumed) == 1, "the resume runs only the remaining segment")
    check(math.isfinite(resumed[0]), "finite loss after restore")


def four_chip_phase():
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.core import (DataFlowKernel, PilotDescription, RPEXExecutor,
                            spmd_app)
    from repro.launch import train

    check(len(jax.devices()) == 4, f"four chips, found {len(jax.devices())}")
    one_step = TRAIN + ["--steps", "1", "--segment", "1", "--no-resume",
                        "--ckpt-every", "1000", "--eval-every", "1000"]
    sharded = train.main(one_step + ["--ckpt-dir", str(SCRATCH / "sh"),
                                     "--data-shards", "2",
                                     "--model-shards", "2"])
    single = train.main(one_step + ["--ckpt-dir", str(SCRATCH / "one")])
    diff = abs(sharded[0] - single[0])
    say(f"first-step loss 2x2 {sharded[0]:.6f} vs one chip "
        f"{single[0]:.6f}: |diff| {diff:.3e} (tolerance {PARITY_TOL})")
    check(diff <= PARITY_TOL, "sharded loss matches one chip")

    rpex = RPEXExecutor(PilotDescription())
    check(rpex.pilot.n_slots == 4, "one slot per chip")
    together = threading.Barrier(4, timeout=120)

    @spmd_app(slots=1, jit=False)
    def where(task_mesh, x):
        together.wait()                 # all four hold a slot at once
        ids = [d.id for d in task_mesh.devices.flat]
        y = jax.device_put(x, NamedSharding(task_mesh, PartitionSpec()))
        return ids, float((y * 2).sum())

    with DataFlowKernel(executors={"rpex": rpex}, run_id=None):
        outs = [f.result(timeout=300)
                for f in [where(jnp.arange(4.0)) for _ in range(4)]]
    rpex.shutdown()
    ids = [o[0] for o in outs]
    say(f"four one-slot SPMD tasks ran on devices {ids}")
    check(sorted(i for o in ids for i in o)
          == sorted(d.id for d in jax.devices()), "four distinct devices")
    check(all(o[1] == 12.0 for o in outs), "SPMD task results")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the multi-chip path (needs four chips)")
    args = ap.parse_args(argv)
    t_start = time.time()
    if not (ROOT / "src" / "repro").is_dir():
        print(f"[smoke] no repro package under {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say(f"device {device}; compile cache {cache_dir}")
    if device["platform"] != "tpu":
        print(f"[smoke] needs a TPU; JAX found {device['platform']}",
              file=sys.stderr)
        return 1

    shutil.rmtree(SCRATCH, ignore_errors=True)
    phases = ([four_chip_phase] if args.four_chips
              else [kernels_phase, train_phase])
    try:
        for phase in phases:
            t = time.time()
            phase()
            say(f"{phase.__name__} passed in {time.time() - t:.1f}s")
    finally:
        shutil.rmtree(SCRATCH, ignore_errors=True)
    stats = devs[0].memory_stats() or {}
    say(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')} on device 0; "
        f"total {time.time() - t_start:.1f}s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
