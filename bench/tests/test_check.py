"""The check that decides ``correct``, driven through the harness.

At the tiny sizes of ``tiny.py`` on the CPU: the look for a chip is skipped
and the rest of a run goes as on the chip, with the cell's own limits.  A
sound run comes out correct; each fault the cell's pattern can have,
planted in the timed path, and the fp8 control, put in the program's
place, come out not correct (one chip: no exchange between chips to leave
out); the control reads well above the program.
"""
import subprocess
import sys

import pytest

from bench import generator, harness
from bench.tests import tiny

ARGS = ["--seed", "2718281828459045", "--seconds", "2", "--trace", "0"]
CELLS = ["train.smollm-360m", "fanout.smollm-360m", "fanout.mamba2-1.3b"]


def _run(name, fault=None, report=None, seed=None):
    args = ARGS if seed is None else ["--seed", str(seed)] + ARGS[2:]
    return harness.run(["--workload", name] + args, allow_cpu=True,
                       fault=fault, cell=tiny.cell(name), report=report)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    res = _run(name)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("name,fault", [
    (c, f) for c in CELLS
    for f in generator.faults(harness.load_cell(c).traffic["pattern"])])
def test_fault_comes_out_not_correct(name, fault):
    res = _run(name, fault)
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_fp8_control_separates_from_the_program(name):
    """The control reads at least three times what the program reads on one
    of the cell's compared numbers, in runs through the harness.  The
    cell's limits are set from both readings at the cell's own size on the
    chip (PERF.md)."""
    prog, ctl = {}, {}
    a = _run(name, report=prog, seed=99)["checks"]
    b = _run(name, generator.CONTROL, report=ctl, seed=99)["checks"]
    limits = tiny.cell(name).workload["limits"]
    ratios = [b[k]["value"] / a[k]["value"] for k in limits]
    assert max(ratios) >= 3.0, (a, b)


def test_unknown_fault_is_refused():
    with pytest.raises(ValueError):
        _run("fanout.smollm-360m", "stale_state")


FOUR_CHIPS = """
import sys
sys.path[:0] = ["src", "."]
import jax
from bench import harness
from bench.tests import tiny
cells = {"fanout.smollm-360m": {"device_slots": 4, "slots_per_task": 1},
         "train.smollm-360m": {"mesh": [2, 2]}}
for name, params in cells.items():
    cell = tiny.cell(name)
    cell.chips = 4
    cell.workload = dict(cell.workload,
                         params=dict(cell.workload["params"], **params))
    res = harness.run(["--workload", name, "--seed", "31", "--seconds", "2"],
                      allow_cpu=True, cell=cell)
    assert res["device"]["count"] == 4, res["device"]
    print(name, res["correct"], res["attempted"], flush=True)
"""


def test_slots_and_mesh_are_parameters_of_the_mix():
    """On four CPU devices: a fan-out of four one-chip device slots, and
    the train step sharded 2 x 2 over a four-slot task, both set by the
    workload's parameters alone, run and come out correct."""
    import os
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", FOUR_CHIPS], cwd=harness.ROOT,
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = [ln.split() for ln in p.stdout.splitlines()]
    assert [ln[:2] for ln in lines] == [["fanout.smollm-360m", "True"],
                                       ["train.smollm-360m", "True"]]
    assert all(int(ln[2]) > 0 for ln in lines)


def _bench_run(cwd, env_extra=None):
    import os
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "train.smollm-360m",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_with_no_result():
    p = _bench_run(harness.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_benchmark_alone_cannot_run(tmp_path):
    """In a directory that holds only BENCHMARK.json and bench/, there is
    no program to run: non-zero exit, no result."""
    import shutil
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    code = ("import sys; sys.path[:0] = ['src', '.']\n"
            "from bench import harness\n"
            "print(harness.run(['--workload', 'train.smollm-360m', '--seed',"
            " '1', '--seconds', '1'], allow_cpu=True))")
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                       capture_output=True, text=True, timeout=300,
                       env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "No module named 'repro'" in p.stderr
