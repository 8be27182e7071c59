"""Experiment 1 (paper Table II / Fig. 4): SPMD-function-executor scaling.

Weak and strong scaling of the MPI-function-executor analog: a homogeneous
workload of no-op SPMD functions, each spanning ``ranks_per_task`` slots
(the paper uses 2-node tasks = 256/112 ranks; we use multi-slot sub-mesh
tasks).  Metrics exactly as the paper defines them:

  TPT — total processing time: last task end - first task start (the time
        the executor kept resources busy);
  TS  — throughput = tasks / TPT.

Two platform profiles mirror Expanse (2..32 "nodes") and Frontera
(8..512 "nodes"), with nodes -> slot blocks.  ``--no-cache`` reproduces the
paper's cold-communicator cost (every task pays trace+compile, the ibrun /
MPI_Comm_split analog); the default cached mode is the paper's own proposed
fix, measured.
"""
from __future__ import annotations

import argparse
import statistics
import time

import jax
import jax.numpy as jnp
from jax import shard_map

from repro.core import (PilotDescription, RPEXExecutor, ResourceSpec,
                        TaskState, translate)


def _noop_spmd(mesh, x):
    # "no-op" MPI function: one tiny collective to force real dispatch
    from jax.sharding import PartitionSpec as P
    return shard_map(lambda a: jax.lax.psum(a, "data"),
                         mesh=mesh, in_specs=P(), out_specs=P())(x)


_noop_spmd.__app_kind__ = "spmd"      # translated as an SPMD task body


def run_scale(n_slots: int, n_tasks: int, ranks_per_task: int,
              cache: bool, repeats: int = 3):
    tpts, tss = [], []
    for _ in range(repeats):
        rpex = RPEXExecutor(PilotDescription(
            n_slots=n_slots, cache_executables=cache,
            max_workers=max(32, n_slots)))
        tm = rpex.tmgr
        tasks = [translate(_noop_spmd, (jnp.float32(i),), {},
                           ResourceSpec(slots=ranks_per_task))
                 for i in range(n_tasks)]
        t0 = time.monotonic()
        tm.submit_bulk(tasks)
        ok = tm.wait(timeout=600)
        assert ok, "timeout"
        starts = [t.timestamps.get("SCHEDULED", t.timestamps["TRANSLATED"])
                  for t in tasks]
        ends = [t.timestamps[t.state.value] for t in tasks]
        assert all(t.state == TaskState.DONE for t in tasks), \
            [t.state for t in tasks if t.state != TaskState.DONE][:3]
        tpt = max(ends) - min(starts)
        tpts.append(tpt)
        tss.append(n_tasks / tpt if tpt > 0 else float("inf"))
        rpex.shutdown()
    return (statistics.mean(tpts), statistics.stdev(tpts) if repeats > 1 else 0.0,
            statistics.mean(tss), statistics.stdev(tss) if repeats > 1 else 0.0)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--profile", choices=["expanse", "frontera", "quick"],
                    default="quick")
    ap.add_argument("--no-cache", action="store_true")
    ap.add_argument("--tasks-per-slot", type=int, default=4)
    ap.add_argument("--strong-tasks", type=int, default=256)
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args(argv)

    profiles = {
        # nodes -> slots (node = 1 slot block here); ranks/task like the
        # paper's 2-node tasks
        "expanse": dict(nodes=[2, 4, 8, 16, 32], ranks=2),
        "frontera": dict(nodes=[8, 16, 32, 64, 128, 256, 512], ranks=2),
        "quick": dict(nodes=[2, 4, 8, 16], ranks=2),
    }
    prof = profiles[args.profile]
    cache = not args.no_cache
    rows = []
    print("system,scaling,nodes,tasks,tpt_s,tpt_sd,ts_tasks_per_s,ts_sd")
    for scaling in ("strong", "weak"):
        for n in prof["nodes"]:
            n_tasks = (args.strong_tasks if scaling == "strong"
                       else n * args.tasks_per_slot)
            tpt, tpt_sd, ts, ts_sd = run_scale(
                n, n_tasks, prof["ranks"], cache, args.repeats)
            row = (args.profile, scaling, n, n_tasks, round(tpt, 4),
                   round(tpt_sd, 4), round(ts, 2), round(ts_sd, 2))
            rows.append(row)
            print(",".join(str(x) for x in row), flush=True)
    return rows


if __name__ == "__main__":
    main()
