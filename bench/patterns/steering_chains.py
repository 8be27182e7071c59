"""``steering_chains``: a Colmena-style steering loop, closed.

The thinker keeps ``in_flight`` chains in flight and submits a new one as
each finishes.  A chain is ``prepare`` (python task: draw ``candidates``
sequences from the corpus), ``score`` (spmd task: the jitted forward and
per-sequence loss, the parameters passed as an argument, as Colmena passes
its model to inference tasks) and ``select`` (python task: the ``top_k``
lowest losses).  Chain i has the length ``cycle[(i + r) % len(cycle)]``:
every seed runs the same cycle of lengths, from a rotation r drawn from
the seed, on rows of the corpus drawn from the seed.  A score task holds
``slots_per_task`` device slots (default: every slot) and runs on the
first chip of them; the parameters are copied to every chip at set-up.

The check compares, on ``checked_chains`` chains drawn from the seed among
those done in the window (one of the longest length among them), each
candidate's mean loss with the float32 reference's, and every chain's
``select`` with the top ``top_k`` of its own losses.

Faults: ``altered_score`` (one loss of each score changed where it is
produced) and ``altered_select`` (the highest losses picked).
"""
from __future__ import annotations

import queue
import time

import numpy as np

from bench import generator as G
from bench import harness, weights
from bench.harness import span
from bench.reference import corpus

FAULTS = ("altered_score", "altered_select")


class Pattern:
    def __init__(self, cell, seed, devices, fault=None):
        self.cell, self.seed, self.devices = cell, seed, devices
        self.p = G.params(cell)
        self.fault = fault
        self.cfg = harness.program_config(cell.config)
        self.vocab = cell.config["sizes"]["vocab_size"]
        self.lengths = sorted(set(self.p["cycle"]))
        self.phases = G.Phases()

    def chain_spec(self, i: int) -> tuple:
        """(length, corpus cursor) of chain i."""
        cycle = self.p["cycle"]
        rot = int(np.random.default_rng((self.seed, 1 << 21))
                  .integers(len(cycle)))
        blk, pos = divmod(i, len(cycle))
        cursors = np.random.default_rng((self.seed, blk)).integers(
            0, 2 ** 40, size=len(cycle))
        return cycle[(i + rot) % len(cycle)], int(cursors[pos])

    def setup(self):
        import jax
        import jax.numpy as jnp

        from repro.core import python_app, spmd_app
        from repro.models import model as M
        from repro.models import transformer as T

        cfg, p = self.cfg, self.p
        self.rpex, self.dfk = G.workflow(self.devices, p)
        seed, vocab, k = self.seed, self.vocab, p["candidates"]
        fault = self.fault

        def score(params, tokens, targets):
            x = M.embed_inputs(cfg, params, {"tokens": tokens})
            h, _, _ = T.forward(cfg, params, x, mode="train")
            logits = M.lm_logits(cfg, params, h).astype(jnp.float32)
            gold = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
            return (jax.nn.logsumexp(logits, axis=-1) - gold).mean(axis=1)

        score_program = jax.jit(score)

        @python_app
        def prepare(length, cursor):
            with span("prepare"):
                tok, tgt = corpus.rows(seed, vocab, cursor, k, length)
                return {"tokens": tok, "targets": tgt}

        @spmd_app(slots=G.slots_per_task(self.devices, p), jit=False)
        def score_task(task_mesh, params, cand):
            chip = task_mesh.devices.flat[0].id
            out = score_program(params[chip], cand["tokens"],
                                cand["targets"])
            if fault == "altered_score":
                out = out.at[0].add(1.0)
            return out

        @python_app
        def select(losses):
            with span("select"):
                losses = np.asarray(losses, np.float64)
                picked = np.argsort(losses, kind="stable")[: p["top_k"]]
                if fault == "altered_select":
                    picked = np.argsort(-losses, kind="stable")[: p["top_k"]]
                return {"picked": picked.tolist(), "losses": losses.tolist()}

        self.apps = (prepare, score_task, select)
        self.phases.mark("workflow")
        made = weights.maker(T.abstract_params(cfg))(weights.seed_key(seed))
        self.params = {d.id: jax.device_put(made, d) for d in self.devices}
        self.phases.mark("weights")
        # every length on every chip, then every length through the path
        for d in self.devices:
            for L in self.lengths:
                z = np.zeros((k, L), np.int32)
                score_program(self.params[d.id], z, z).block_until_ready()
        for i, L in enumerate(self.lengths * p["warm_chains_per_length"]):
            self._submit((L, i))[-1].result()
        self.phases.mark("warm")

    def _submit(self, spec):
        """(prepare, score, select) futures of one chain."""
        prepare, score_task, select = self.apps
        with span("submit"):
            pf = prepare(*spec)
            sf = score_task(self.params, pf)
            return pf, sf, select(sf)

    def window(self, seconds):
        p = self.p
        done_q = queue.Queue()
        chains = {}                     # i -> dict
        submitted = 0

        def launch(i):
            spec = self.chain_spec(i)
            t = time.monotonic()
            futs = self._submit(spec)
            fut = futs[-1]
            chains[i] = {"spec": spec, "t_submit": t, "fut": fut,
                         "futs": futs}
            fut.add_done_callback(
                lambda f, i=i: done_q.put((i, time.monotonic())))

        with span("window"):
            t0 = time.monotonic()
            t_end = t0 + seconds
            for _ in range(p["in_flight"]):
                launch(submitted)
                submitted += 1
            outstanding = p["in_flight"]
            while outstanding:
                with span("await_result"):
                    i, t_done = done_q.get()
                chains[i]["t_done"] = t_done
                outstanding -= 1
                if time.monotonic() < t_end:
                    launch(submitted)
                    submitted += 1
                    outstanding += 1
        t1 = t0 + seconds
        done, failed, lat = [], 0, []
        for i, c in sorted(chains.items()):
            try:
                c["out"] = c["fut"].result()
            except Exception:                      # counted, reported
                failed += 1
                continue
            if c["t_done"] <= t1:
                done.append(i)
                lat.append((c["t_done"] - c["t_submit"]) * 1e3)
        self.chains, self.done = chains, done
        ends = sorted([t0] + [chains[i]["t_done"] for i in done])
        wait = float(max(np.diff(ends))) * 1e3 if len(ends) > 1 else None
        self.info = {"longest_wait_between_chains_ms": (wait, None)}
        edges, score_uids = [], []
        for i in done:
            pf, sf, sel = chains[i]["futs"]
            edges += [(pf.task.uid, sf.task.uid), (sf.task.uid, sel.task.uid)]
            score_uids.append(sf.task.uid)
        return {"t0": t0, "t1": t1, "attempted": submitted,
                "failed": failed, "chains": len(done),
                "score_tasks": len(done),
                "score_tokens": sum(chains[i]["spec"][0] for i in done)
                * p["candidates"],
                "edges": edges, "score_uids": score_uids,
                "end_to_end": {"chains_per_s": len(done) / seconds,
                               "chain_p95_ms": harness.quantile(lat, 0.95)},
                "timelines": G.timelines(self.rpex)}

    def close(self):
        self.dfk.__exit__(None, None, None)
        G.free(self.params)
        self.params = None

    def sample(self) -> list:
        """Chains the check compares, drawn from the seed among those done
        in the window, one of them of the longest length."""
        p = self.p
        rng = np.random.default_rng((self.seed, 1 << 20))
        pool = list(self.done)
        picked = [int(i) for i in rng.choice(
            pool, size=min(p["checked_chains"], len(pool)), replace=False)]
        longest = [i for i in pool
                   if self.chains[i]["spec"][0] == max(self.lengths)]
        if picked and longest and not any(i in longest for i in picked):
            picked[-1] = longest[int(rng.integers(len(longest)))]
        return picked

    def reference(self, chains, prec="f32") -> dict:
        """{chain: the reference's mean loss of each candidate}."""
        import jax
        import jax.numpy as jnp

        from bench import reference
        from repro.models import transformer as T
        conf, p = self.cell.config, self.p
        w = weights.flat(weights.maker(T.abstract_params(self.cfg))(
            weights.seed_key(self.seed)))
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        model = reference.model(conf["reference"])
        cfg_ref = G.ref_model(conf)

        @jax.jit
        def losses(w, tok, tgt):
            with jax.default_matmul_precision("highest"):
                return model.token_nll(w, tok, tgt, cfg_ref,
                                       prec).mean(axis=1)

        out = {}
        for i in chains:
            L, cursor = self.chains[i]["spec"]
            tok, tgt = corpus.rows(self.seed, self.vocab, cursor,
                                   p["candidates"], L)
            out[i] = np.asarray(losses(w, tok, tgt), np.float64)
        return out

    def check(self):
        """Under the control, the reference in fp8 stands in for the
        program's losses."""
        p = self.p
        select_errors = 0
        for i in self.done:
            out = self.chains[i]["out"]
            want = np.argsort(np.asarray(out["losses"]),
                              kind="stable")[: p["top_k"]].tolist()
            select_errors += out["picked"] != want
        sample = self.sample()
        ref = self.reference(sample)
        got = (self.reference(sample, "fp8") if self.fault == G.CONTROL
               else {i: np.asarray(self.chains[i]["out"]["losses"])
                     for i in sample})
        return [{"name": "score_gap", "value": score_gap(got, ref),
                 "limit": G.limits(self.cell)["score_gap"]},
                {"name": "select_errors", "value": int(select_errors),
                 "limit": 0},
                {"name": "chains_unchecked",
                 "value": p["checked_chains"] - len(sample), "limit": 0}]


def score_gap(got: dict, ref: dict) -> float:
    """Widest gap between a candidate's mean loss and the reference's."""
    return G.worst(np.max(np.abs(got[i] - ref[i])) for i in ref)
