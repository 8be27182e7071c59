"""The traffic generator: what every pattern shares, and the patterns' loader.

A traffic file (``bench/traffic/<mix>.json``) is data: it names its
``pattern`` and gives its parameters; a cell's workload file may override
them under ``params``.  A pattern is a module of its own,
``bench/patterns/<pattern>.py``, found by that name, with a class
``Pattern`` and a tuple ``FAULTS`` of the faults its cells can have.  A
mix that an existing pattern can drive is added as a data file alone; a
new kind of traffic is a new pattern module beside the others.

Each pattern assembles its workflow from the program's public API the way
``repro.launch.train`` does: a DataFlowKernel over an RPEXExecutor with a
device pilot of ``device_slots`` slots (one per chip unless the mix says
otherwise) for ``spmd_app`` tasks and a host pilot of ``host_slots`` slots
for ``python_app`` tasks.  A pattern has four phases: ``setup`` (weights
from the seed, every shape of the cell warmed, and the first steps or
chains the check needs), ``window`` (the measured seconds), ``close`` (the
workflow shut down and the program's device state freed) and ``check``
(the comparison with the float32 reference: a list of numbers, each with
its limit).

Every pattern also knows ``control_fp8``: the float32 reference computed
with fp8 matrix products (one precision below the configurations'
bfloat16) put in the program's place, which the check has to fail.
"""
from __future__ import annotations

import importlib.util
import time
from pathlib import Path

import numpy as np

PATTERNS = Path(__file__).resolve().parent / "patterns"
CONTROL = "control_fp8"


def load_pattern(name: str):
    """The module ``bench/patterns/<name>.py``."""
    path = PATTERNS / f"{name}.py"
    if not path.is_file():
        known = sorted(p.stem for p in PATTERNS.glob("*.py"))
        raise SystemExit(f"no traffic pattern {name!r}; known: {known}")
    spec = importlib.util.spec_from_file_location(f"bench_pattern_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def faults(name: str) -> tuple:
    """Every fault kind a run of the pattern can be given: its own, then
    the control."""
    return tuple(load_pattern(name).FAULTS) + (CONTROL,)


def params(cell) -> dict:
    return {**cell.traffic, **cell.workload.get("params", {})}


def limits(cell) -> dict:
    return cell.workload["limits"]


def ref_model(conf) -> dict:
    return {k: conf["sizes"][k] for k in conf["reference_sizes"]}


class Phases:
    """Seconds of each named phase of set-up, on the host clock."""

    def __init__(self):
        self.t, self.s = time.monotonic(), {}

    def mark(self, name: str):
        now = time.monotonic()
        self.s[name] = self.s.get(name, 0.0) + now - self.t
        self.t = now


def workflow(devices, p):
    """(rpex, dfk): the device pilot (``device_slots``, default one slot per
    chip) and the host pilot (``host_slots``), the DFK entered."""
    from repro.core import DataFlowKernel, PilotDescription, RPEXExecutor
    rpex = RPEXExecutor([
        PilotDescription(n_slots=p.get("device_slots", len(devices)),
                         devices=list(devices), kinds=("spmd",),
                         name="device"),
        PilotDescription(n_slots=p["host_slots"], devices=list(devices[:1]),
                         kinds=("python",), name="host")])
    dfk = DataFlowKernel(executors={"rpex": rpex})
    dfk.__enter__()
    return rpex, dfk


def slots_per_task(devices, p) -> int:
    return p.get("slots_per_task", p.get("device_slots", len(devices)))


def timelines(rpex) -> dict:
    out = {}
    for pilot in rpex.pool.all_pilots():
        out.update(pilot.store.timeline())
    return out


def free(tree):
    import jax
    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, jax.Array) and not leaf.is_deleted():
            leaf.delete()


def worst(values) -> float:
    """The largest of ``values``; infinite where one is not a number, so
    that a NaN fails every limit."""
    values = [float(v) for v in values]
    if not values or not all(np.isfinite(values)):
        return float("inf")
    return max(values)


def leaf_gap(prog: dict, ref: dict, keep=None) -> tuple:
    """Worst leaf's |norm(program) - norm(reference)|, over the reference's
    norm of that leaf or of the median leaf, whichever is larger."""
    names = [k for k in ref if keep is None or k in keep]
    floor = float(np.median([ref[k] for k in names]))
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], floor, 1e-30)
            for k in names}
    bad = [k for k in names if not np.isfinite(gaps[k])]
    if bad:
        return float("inf"), bad[0]
    top = max(gaps, key=gaps.get)
    return gaps[top], top


def moving_leaves(grad_norms: dict, share: float) -> set:
    """Leaves whose reference gradient is at least ``share`` of the median
    leaf's: the others move under Adam by round-off alone."""
    med = float(np.median(list(grad_norms.values())))
    return {k for k, v in grad_norms.items() if v >= share * med}
