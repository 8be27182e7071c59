"""Tiny stand-ins of the benchmark's configurations, for CPU tests.

Same registries, files and traffic as the real cells, at widths a CPU runs
in seconds.  ``cell(name)`` gives a cell object for ``harness.run(cell=)``.

The stand-ins are data files found by name under ``bench/tests/tiny_data/``:
``configs/<config>.json`` (``program`` and ``sizes``, laid over the real
configuration file), ``workloads/<cell>.json`` (``limits`` at these sizes;
without the file the cell keeps its real limits) and ``traffic/<mix>.json``
(``params`` laid over the mix's).  Tiny limits are set by the rule the real
cells' limits follow (PERF.md), between the program's and the fp8 control's
readings on the CPU; each workload file gives its readings.
"""
from __future__ import annotations

import copy

from bench import harness

TRAIN = {"batch": 2, "seq_len": 32, "reference_rows": 1}


def _data(kind: str, name: str, default=None) -> dict:
    path = harness.BENCH / "tests" / "tiny_data" / kind / f"{name}.json"
    if default is not None and not path.is_file():
        return default
    return harness.load_json(path)


def config(name: str) -> dict:
    conf = copy.deepcopy(harness.load_json(
        harness.BENCH / "configs" / f"{name}.json"))
    tiny = _data("configs", name)
    conf["program"] = dict(conf.get("program", {}), **tiny["program"])
    conf["sizes"].update(tiny["sizes"])
    conf["train"] = dict(TRAIN)
    return conf


def cell(name: str):
    real = harness.load_cell(name)
    real.config = config(real.workload["config"])
    mix = _data("traffic", real.workload["traffic"], {"params": {}})
    limits = _data("workloads", name, {"limits": real.workload["limits"]})
    real.workload = dict(real.workload,
                         params=dict(real.workload.get("params", {}),
                                     **mix["params"]),
                         limits=limits["limits"])
    return real
