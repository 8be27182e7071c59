"""``train_segments``: workflow-managed training.

``train_segment`` spmd tasks of ``steps_per_segment`` optimizer steps each,
fed by the program's ``ShardedLoader``; the host builds each segment's
batches, submits the task and waits for its result, as the trainer's loop
does.  A task holds ``slots_per_task`` device slots (default: every slot);
``mesh`` ([data, model], default one chip) shards the step over the chips
of those slots with the program's partition rules, as ``repro.launch.train``
does with ``--data-shards`` and ``--model-shards``.

The check's first ``checked_steps`` steps go through the window's own task
and feed during set-up.  It compares, by worst leaf, the first gradient's
norm as the optimizer holds it and the norm of the parameters' change
after those steps, against the float32 reference from the same weights and
rows, and the loader's rows against the benchmark's copy of its corpus.

Faults: ``stale_state`` (each step's state comes back unchanged) and
``half_batch`` (each step sees half of its batch rows).
"""
from __future__ import annotations

import time

import numpy as np

from bench import generator as G
from bench import harness, weights
from bench.harness import span
from bench.reference import corpus
from bench.reference import train as ref_train

FAULTS = ("stale_state", "half_batch")


class Pattern:
    def __init__(self, cell, seed, devices, fault=None):
        self.cell, self.seed, self.devices = cell, seed, devices
        self.p = G.params(cell)
        self.fault = fault
        self.cfg = harness.program_config(cell.config)
        self.phases = G.Phases()

    def _mesh(self):
        """(mesh or None, task slots): the step's mesh over the chips a task
        holds; none where that is one chip."""
        from repro.sharding.partition import make_mesh
        shape = tuple(self.p.get("mesh", (1, 1)))
        n = G.slots_per_task(self.devices, self.p)
        if int(np.prod(shape)) != n:
            raise SystemExit(f"{self.cell.name}: mesh {list(shape)} needs "
                             f"{int(np.prod(shape))} slots a task, not {n}")
        if n == 1:
            return None, 1
        return make_mesh(shape, devices=list(self.devices[:n])), n

    # ------------------------------------------------------------------ #
    def setup(self):
        import jax
        import jax.numpy as jnp

        from repro.core import spmd_app
        from repro.data.pipeline import DataConfig, ShardedLoader
        from repro.models import model as M
        from repro.models import transformer as T
        from repro.optim import AdamState, AdamW, cosine_schedule
        from repro.sharding.partition import PartitionRules, ShardCtx

        cfg, p, o = self.cfg, self.p, self.p["optimizer"]
        self.batch, self.seq = self.cell.config["train"]["batch"], \
            self.cell.config["train"]["seq_len"]
        self.rpex, self.dfk = G.workflow(self.devices, p)
        mesh, slots = self._mesh()
        rules = PartitionRules()
        opt = AdamW(lr=cosine_schedule(o["lr"], o["warmup_steps"],
                                       o["total_steps"]),
                    b1=o["b1"], b2=o["b2"], eps=o["eps"],
                    weight_decay=o["weight_decay"], clip_norm=o["clip_norm"])
        step = M.make_train_step(cfg, opt, ShardCtx(mesh, rules))
        jit_step = jax.jit(step, donate_argnums=(0, 1))
        fault = self.fault
        self.phases.mark("workflow")

        @spmd_app(slots=slots, jit=False)
        def train_segment(task_mesh, params, opt_state, batches):
            metrics = None
            for b in batches:
                if fault == "half_batch":
                    b = jax.tree.map(lambda x: x[: x.shape[0] // 2], b)
                if fault == "stale_state":      # the state comes back as it was
                    copy = jax.tree.map(jnp.copy, (params, opt_state))
                    _, _, metrics = jit_step(*copy, b)
                    continue
                params, opt_state, metrics = jit_step(params, opt_state, b)
            return params, opt_state, metrics

        self.train_segment = train_segment
        shardings = opt_shardings = None
        if mesh is not None:
            shard = lambda s: jax.NamedSharding(mesh, s)  # noqa: E731
            shardings = jax.tree.map(shard, T.param_pspecs(cfg, mesh, rules))
            opt_shardings = AdamState(shard(jax.sharding.PartitionSpec()),
                                      shardings, shardings)
        self.make = weights.maker(T.abstract_params(cfg), shardings)
        key = weights.seed_key(self.seed)
        norms = jax.jit(lambda t: {k: ref_train.norm_rows(k, v)
                                   for k, v in weights.flat(t).items()})
        delta = jax.jit(lambda t, t0: {
            n: ref_train.norm_rows(n, v.astype(jnp.float32)
                                   - w.astype(jnp.float32))
            for (n, v), w in zip(weights.flat(t).items(),
                                 weights.flat(t0).values())})
        params = self.make(key)
        opt_state = jax.jit(opt.init, out_shardings=opt_shardings)(params)
        self.loader = ShardedLoader(DataConfig(
            vocab_size=cfg.vocab_size, seq_len=self.seq,
            global_batch=self.batch, seed=self.seed))
        self.phases.mark("weights")

        # the check's first steps, through the window's own call and feed
        self.first_rows, self.first_losses = [], []
        for i in range(p["checked_steps"]):
            batch = next(self.loader)
            self.first_rows.append((batch["tokens"], batch["targets"]))
            fut = train_segment(params, opt_state,
                                [jax.tree.map(jnp.asarray, batch)])
            params, opt_state, metrics = fut.result()
            self.first_losses.append(float(metrics["loss"]))
            if i == 0:
                m = ref_train.expand(norms(opt_state.m))
                self.first_grad = {k: v / (1.0 - o["b1"])
                                   for k, v in m.items()}
        # against the weights as first made: made again inside another
        # program, they may round differently on the chip
        self.first_delta = ref_train.expand(delta(params, self.make(key)))
        self.params, self.opt_state = params, opt_state
        self.tokens_per_segment = (p["steps_per_segment"] * self.batch
                                   * self.seq)
        self.phases.mark("checked_steps")

    # ------------------------------------------------------------------ #
    def window(self, seconds):
        import jax
        import jax.numpy as jnp
        n = self.p["steps_per_segment"]
        params, opt_state = self.params, self.opt_state
        segments = failed = 0
        with span("window"):
            t0 = time.monotonic()
            t_end = t0 + seconds
            t_last = t0
            while time.monotonic() < t_end:
                with span("build_batch"):
                    batches = [jax.tree.map(jnp.asarray, next(self.loader))
                               for _ in range(n)]
                with span("submit"):
                    fut = self.train_segment(params, opt_state, batches)
                with span("await_result"):
                    try:
                        params, opt_state, metrics = fut.result()
                        float(metrics["loss"])
                        segments += 1
                    except Exception:                  # counted, reported
                        failed += 1
                        break
                t_last = time.monotonic()
        self.params, self.opt_state = params, opt_state
        tokens = segments * self.tokens_per_segment
        rate = tokens / (t_last - t0) if segments else 0.0
        return {"t0": t0, "t1": t_last, "attempted": segments + failed,
                "failed": failed, "steps": segments * n, "tokens": tokens,
                "end_to_end": {"train_tokens_per_s": rate},
                "timelines": G.timelines(self.rpex)}

    def close(self):
        self.loader.close()
        self.dfk.__exit__(None, None, None)
        G.free((self.params, self.opt_state))
        self.params = self.opt_state = None

    # ------------------------------------------------------------------ #
    def reference(self, prec="f32", half=False):
        """The reference's readings over the rows of the checked steps;
        also counts the rows the program's loader gave otherwise."""
        conf, p = self.cell.config, self.p
        batches, self.rows_differ = [], 0
        for i, (tok, tgt) in enumerate(self.first_rows):
            want = corpus.rows(self.seed, conf["sizes"]["vocab_size"],
                               i * self.batch * (self.seq + 1), self.batch,
                               self.seq)
            self.rows_differ += int(np.sum(np.any(tok != want[0], axis=1)
                                           | np.any(tgt != want[1], axis=1)))
            batches.append(want)
        key = weights.seed_key(self.seed)
        return ref_train.follow(conf["reference"], G.ref_model(conf),
                                dict(p["optimizer"],
                                     param_dtype=conf["param_dtype"]),
                                lambda: weights.flat(self.make(key)), batches,
                                prec=prec, rows=conf["train"]["reference_rows"],
                                half=half)

    def readings(self) -> dict:
        return {"losses": self.first_losses, "grad_norms": self.first_grad,
                "delta_norms": self.first_delta}

    def check(self):
        """The readings the workload file gives a limit are compared; the
        others are reported (``info``).  Under the control, the reference in
        fp8 stands in for the program's readings."""
        lim = G.limits(self.cell)
        ref = self.reference()
        prog = (self.reference("fp8") if self.fault == G.CONTROL
                else self.readings())
        got = train_readings(prog, ref, self.p["nought_grad_share"])
        self.info = {k: v for k, v in got.items() if k not in lim}
        return [{"name": k, "value": v, "limit": lim[k]}
                for k, (v, _) in got.items() if k in lim] + [
            {"name": "input_rows_differ", "value": self.rows_differ,
             "limit": 0}]


def train_readings(prog: dict, ref: dict, share: float) -> dict:
    """Every number a training cell can compare: {name: (value, detail)}.
    ``loss_gap`` is the widest gap of the checked steps' losses; the norm
    gaps are by worst leaf (the leaf is the detail)."""
    steps = [abs(a - b) for a, b in zip(prog["losses"], ref["losses"])]
    keep = G.moving_leaves(ref["grad_norms"], share)
    return {
        "loss_gap": (G.worst(steps), steps),
        "grad_norm_gap": G.leaf_gap(prog["grad_norms"], ref["grad_norms"]),
        "update_norm_gap": G.leaf_gap(prog["delta_norms"],
                                      ref["delta_norms"], keep)}
