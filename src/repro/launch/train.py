"""End-to-end training driver, workflow-managed.

The training job is expressed as an RPEX workflow (the paper's model): the
device pilot runs `train_segment` SPMD tasks (N optimizer steps each), while
single-slot Python tasks on a host pilot handle evaluation and checkpoint
commits concurrently — the heterogeneous-task mix of the Colmena use case,
applied to an LM pre-training job.

The device pilot holds one slot per device (``--slots`` may add spares); a
segment takes as many slots as its mesh spans (one without a mesh).  The
host pilot's slots are its own, so a commit never holds up the next segment.

Fault tolerance: auto-resume from the newest checkpoint (params, optimizer
state, data cursor; ``--no-resume`` starts over); ``--inject-failure``
kills device slots mid-run to exercise reschedule onto spare slots, and is
refused when no slot block for a segment would be left.

Example (CPU, reduced config):
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
      --reduced --steps 200 --segment 20 --batch 8 --seq 256
"""
from __future__ import annotations

import argparse
import time
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpoint import Checkpointer
from repro.configs import get_config, reduce_config
from repro.core import (DataFlowKernel, PilotDescription, RPEXExecutor,
                        SlotScheduler, python_app, spmd_app)
from repro.data.pipeline import DataConfig, ShardedLoader
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.models import transformer as T
from repro.optim import AdamW, cosine_schedule
from repro.sharding.partition import PartitionRules, ShardCtx, make_mesh


def build_state(cfg, mesh, rules, seed=0):
    params = T.init_params(cfg, jax.random.PRNGKey(seed))
    opt = AdamW(lr=cosine_schedule(3e-4, 20, 10_000))
    opt_state = opt.init(params)
    if mesh is not None:
        pspecs = T.param_pspecs(cfg, mesh, rules)
        shard = lambda t, s: jax.device_put(t, jax.NamedSharding(mesh, s))
        params = jax.tree.map(shard, params, pspecs)
        opt_state = type(opt_state)(
            jax.device_put(opt_state.step),
            jax.tree.map(shard, opt_state.m, pspecs),
            jax.tree.map(shard, opt_state.v, pspecs))
    return params, opt, opt_state


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--segment", type=int, default=10,
                    help="steps per train_segment task")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--eval-every", type=int, default=50)
    ap.add_argument("--slots", type=int, default=0,
                    help="device pilot slots (0 = one per mesh device)")
    ap.add_argument("--data-shards", type=int, default=1)
    ap.add_argument("--model-shards", type=int, default=1)
    ap.add_argument("--inject-failure", type=int, default=0,
                    help="kill this many device slots mid-run (fault "
                         "drill; needs spare --slots)")
    ap.add_argument("--resume", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg)
    rules = PartitionRules()

    n_shards = args.data_shards * args.model_shards
    n_slots = args.slots or n_shards
    devices = jax.devices()[:max(n_slots, n_shards)]
    if len(devices) < n_shards:
        raise ValueError(f"{args.data_shards}x{args.model_shards} shards "
                         f"need {n_shards} devices; {len(devices)} visible")
    if devices[0].platform != "cpu" and len(devices) < n_slots:
        raise ValueError(f"--slots {n_slots}: a slot is a device, and "
                         f"{len(devices)} are visible")
    if args.inject_failure:
        probe = SlotScheduler(n_slots)
        probe.mark_failed(range(args.inject_failure))
        if probe.allocate("segment", n_shards) is None:
            raise ValueError(
                f"--inject-failure {args.inject_failure} leaves no block of "
                f"{n_shards} of the {n_slots} device slots for a segment; "
                f"raise --slots")
    # device pilot first (rpex.pilot): the segments' slots, one per device;
    # the host pilot runs the eval and checkpoint helpers beside them
    rpex = RPEXExecutor([
        PilotDescription(n_slots=n_slots, devices=devices, kinds=("spmd",),
                         name="device"),
        PilotDescription(n_slots=2, devices=devices[:1], kinds=("python",),
                         name="host")])
    mesh = (make_mesh((args.data_shards, args.model_shards),
                      devices=devices[:n_shards]) if n_shards > 1 else None)
    sctx = ShardCtx(mesh, rules)

    params, opt, opt_state = build_state(cfg, mesh, rules)
    ckpt = Checkpointer(args.ckpt_dir)
    loader_cursor = 0
    start_step = 0
    if args.resume and ckpt.latest_step() is not None:
        start_step, (params, opt_state, cursor_arr) = ckpt.restore(
            (params, opt_state, np.zeros((), np.int64)))
        loader_cursor = int(cursor_arr)
        print(f"[train] resumed from step {start_step} "
              f"(data cursor {loader_cursor})")

    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                      global_batch=args.batch,
                      frontend_tokens=cfg.frontend_tokens if
                      cfg.frontend == "vision_stub" else 0,
                      d_model=cfg.d_model)
    loader = ShardedLoader(dcfg, start_cursor=loader_cursor)

    step_fn = M.make_train_step(cfg, opt, sctx,
                                microbatches=args.microbatches)
    jit_step = jax.jit(step_fn, donate_argnums=(0, 1))

    eval_loss = jax.jit(lambda p, b: M.loss_fn(cfg, p, b, sctx)[0])

    @spmd_app(slots=n_shards, jit=False)
    def train_segment(task_mesh, params, opt_state, batches):
        # segment body drives the pre-jitted step; task_mesh is the carved
        # sub-mesh (the actual sharded mesh is managed by jit_step's specs)
        metrics = None
        for b in batches:
            params, opt_state, metrics = jit_step(params, opt_state, b)
        return (params, opt_state, metrics,
                tuple(d.id for d in task_mesh.devices.flat))

    @python_app
    def evaluate(params, batch):
        return float(eval_loss(params, batch))

    @python_app
    def commit_checkpoint(step, params, opt_state, cursor):
        ckpt.save(step, (params, opt_state, np.int64(cursor)))
        return step

    t0 = time.time()
    losses = []
    with DataFlowKernel(executors={"rpex": rpex}, run_id=None) as dfk:
        step = start_step
        pending = []
        evals = []
        failed_injected = False
        while step < args.steps:
            n = min(args.segment, args.steps - step)
            batches = [jax.tree.map(jnp.asarray, next(loader))
                       for _ in range(n)]
            t_seg = time.time()
            fut = train_segment(params, opt_state, batches)
            params, opt_state, metrics, seg_devices = fut.result()
            step += n
            loss = float(metrics["loss"])
            losses.append(loss)
            seg_s = time.time() - t_seg
            print(f"[train] step {step:5d} loss {loss:.4f} segment "
                  f"{seg_s:.2f}s ({seg_s / n:.3f}s/step) on devices "
                  f"{list(seg_devices)} ({(time.time()-t0):.1f}s)",
                  flush=True)
            if args.inject_failure and not failed_injected and \
                    step >= args.steps // 2:
                failed_injected = True
                victims = rpex.pilot.agent.inject_slot_failure(
                    list(range(args.inject_failure)))
                print(f"[train] injected failure on "
                      f"{args.inject_failure} slots (victims: {victims})")
            if step % args.ckpt_every == 0 or step >= args.steps or \
                    step % args.eval_every == 0:
                # host snapshot BEFORE the next segment donates these buffers
                snap_p = jax.tree.map(np.asarray, params)
            if step % args.ckpt_every == 0 or step >= args.steps:
                snap_o = jax.tree.map(np.asarray, opt_state)
                pending.append(commit_checkpoint(step, snap_p, snap_o,
                                                 loader.cursor))
            if step % args.eval_every == 0:
                eb = jax.tree.map(jnp.asarray, next(loader))
                evals.append((step, evaluate(snap_p, eb)))
        for f in pending:
            f.result()
        for at, f in evals:
            print(f"[train] eval at step {at}: loss {f.result():.4f}")
    loader.close()
    stats = rpex.pilot.executor.stats
    print(f"[train] executor: {stats['compiles']} compiles, "
          f"{stats['specializations']} specializations, "
          f"{stats['cache_hits']} cache hits")
    rpex.shutdown()
    if losses:
        print(f"[train] done: {step} steps, final loss {losses[-1]:.4f}, "
              f"first loss {losses[0]:.4f}")
    else:
        # resumed past --steps: every segment was skipped via checkpoint
        print(f"[train] done: already at step {step}, nothing to run")
    return losses


if __name__ == "__main__":
    main()
