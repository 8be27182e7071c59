"""The reduction from a profiler trace to busy, idle and gap names."""
import gzip
import json
from pathlib import Path

import pytest

from bench import trace as tr

MS = 1e6
DATA = Path(__file__).resolve().parent / "data"


def _t(ops, host=(), window=(0, 100 * MS)):
    return tr.Trace([{"ops": list(ops), "modules": []}],
                    [("window", window[0], window[1] - window[0])]
                    + list(host))


def test_busy_is_the_union_clipped_to_the_window():
    t = _t([("a", -10 * MS, 20 * MS), ("b", 5 * MS, 10 * MS),
            ("c", 95 * MS, 20 * MS)])
    assert t.busy_s() == pytest.approx(0.020)        # [0, 15] and [95, 100]
    assert t.idle_pct() == pytest.approx(80.0)


def test_idle_gaps_are_named_by_the_innermost_open_span():
    t = _t([("a", 0, 10 * MS), ("b", 40 * MS, 60 * MS)],
           host=[("await_result", 0, 100 * MS),
                 ("build_batch", 12 * MS, 20 * MS)])
    assert t.idle_gaps() == [["build_batch", pytest.approx(0.030)]]


def test_top_ops_sum_by_name():
    t = _t([("fusion.1", 0, 10 * MS), ("fusion.1", 20 * MS, 10 * MS),
            ("copy.2", 40 * MS, 5 * MS)])
    assert t.top_ops() == [["fusion.1", pytest.approx(0.02)],
                           ["copy.2", pytest.approx(0.005)]]


def test_container_ops_are_not_operations():
    assert tr.op_name("%while.3 = (s32[]) while((s32[]) %t), "
                      "condition=%c, body=%b") is None
    assert tr.op_name("%fusion.143 = bf16[4,8]{1,0} fusion(bf16[4] %x), "
                      "kind=kOutput, calls=%f") == "fusion.143"


def test_a_trace_needs_its_window():
    with pytest.raises(ValueError):
        tr.Trace([{"ops": [], "modules": []}], [])


@pytest.mark.parametrize("name,idle,gap", [
    ("trace_train_smollm.json.gz", (0.1, 2.0), "build_batch"),
    ("trace_fanout_smollm.json.gz", (10.0, 25.0), "prepare")])
def test_recorded_chip_trace(name, idle, gap):
    """A trimmed trace from a v5e run of a cell (`train.smollm-360m`: 1.6 s
    of its window; `fanout.smollm-360m`: 0.25 s): the reduction gives a busy
    share within the window, named gaps and the cell's program."""
    with gzip.open(DATA / name, "rt") as fh:
        t = tr.from_json(json.load(fh))
    assert 0 < t.busy_s() <= t.window_s
    assert 0 <= t.idle_pct() < 100
    b = t.breakdown()
    assert 0 < len(b["device_ops"]) <= 10
    assert all(sec > 0 for _, sec in b["device_ops"])
    assert all(n in tr.HOST_SPANS + ("none",) for n, _ in b["idle_gaps"])
    assert t.module_s("jit_") > 0
    # as read from this trace when it was recorded: the device's idle share
    # and the host span open during the longest gap
    assert idle[0] < t.idle_pct() < idle[1]
    assert b["idle_gaps"][0][0] == gap
