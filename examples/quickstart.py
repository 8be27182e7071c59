"""Quickstart: heterogeneous workflow on a pilot in ~40 lines.

    PYTHONPATH=src python examples/quickstart.py

Builds a pilot over the visible devices, defines three app kinds (Python,
SPMD-with-collectives, bash), wires them into a dataflow graph through
futures, and runs them under the RPEX executor — the paper's full stack
(DFK -> Task Translator -> Pilot/Agent -> SPMD function executor).
"""
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import (DataFlowKernel, PilotDescription, RPEXExecutor,
                        bash_app, python_app, spmd_app)


@python_app
def make_params(scale):
    return {"scale": scale}


@spmd_app(slots=4, mesh=(4, 1), jit=False)
def parallel_norm(mesh, params, n):
    """An 'MPI function': collective sum over the task's private sub-mesh."""
    x = jnp.arange(float(n)) * params["scale"]
    return shard_map(lambda a: jax.lax.psum(jnp.sum(a * a), "data"),
                         mesh=mesh, in_specs=P("data"), out_specs=P())(x)


@python_app
def report(sq_norm):
    return f"||x||^2 = {float(sq_norm):.1f}"


@bash_app
def archive(msg):
    return f"echo archived: {msg}"


def main():
    rpex = RPEXExecutor(PilotDescription(n_slots=8))
    with DataFlowKernel(executors={"rpex": rpex}):
        params = make_params(2.0)          # python task
        norm = parallel_norm(params, 16)   # SPMD task, depends on params
        msg = report(norm)                 # python task, depends on norm
        arch = archive(msg)                # bash task, depends on msg
        print(msg.result())
        print(arch.result().strip())
    rpex.shutdown()
    print("executor stats:", dict(rpex.pilot.executor.stats))


if __name__ == "__main__":
    main()
