"""Each per-layer metric's reader, on hand-made inputs."""
from types import SimpleNamespace

import pytest

from bench import flops, harness, trace as tr
from bench.tests import tiny

MS = 1e6            # nanoseconds in a millisecond


def _trace(ops, modules=(), host=(), window=(0, 100 * MS)):
    host = [("window", window[0], window[1] - window[0])] + list(host)
    return tr.Trace([{"ops": list(ops), "modules": list(modules)}], host)


def _ctx(name, trace=None, window=None, compiles=0):
    cell = harness.load_cell(name)
    return SimpleNamespace(cell=cell, trace=trace, window=window or {},
                           chips=1, peaks=tr.peaks_for("TPU v5 lite"),
                           window_compiles=compiles)


def test_dep_gap_ms_from_stamps():
    read = harness.load_metric("dep_gap_ms.fanout").read
    tl = {"p": {"DONE": 1.000}, "s": {"TRANSLATED": 1.002, "DONE": 1.010},
          "x": {"TRANSLATED": 1.016}}
    win = {"timelines": tl, "edges": [("p", "s"), ("s", "x")]}
    assert read(_ctx("fanout.smollm-360m", window=win)) == pytest.approx(4.0)


def test_dep_gap_ms_prefers_new_stamp():
    gaps = harness.load_metric("dep_gap_ms.fanout").gaps_ms(
        {"a": {"DONE": 2.0}, "b": {"NEW": 2.001, "TRANSLATED": 2.003}},
        [("a", "b")])
    assert gaps == pytest.approx([1.0])


def test_dep_gap_ms_none_without_edges():
    read = harness.load_metric("dep_gap_ms.fanout").read
    assert read(_ctx("fanout.smollm-360m",
                     window={"timelines": {}, "edges": []})) is None


def test_launch_ms_is_median_scheduled_to_running():
    read = harness.load_metric("launch_ms.fanout").read
    tl = {f"t{i}": {"SCHEDULED": 10.0, "RUNNING": 10.0 + d}
          for i, d in enumerate([0.001, 0.003, 0.010])}
    win = {"timelines": tl, "score_uids": list(tl)}
    assert read(_ctx("fanout.smollm-360m", window=win)) == pytest.approx(3.0)


def test_window_compiles_counts_what_it_is_given():
    read = harness.load_metric("window_compiles.fanout").read
    assert read(_ctx("fanout.smollm-360m", compiles=2)) == 2


def test_device_idle_pct_from_op_union():
    t = _trace([("a", 0, 30 * MS), ("b", 20 * MS, 30 * MS),
                ("c", 80 * MS, 10 * MS)])
    for cell, name in [("train.smollm-360m", "device_idle_pct.train"),
                       ("fanout.smollm-360m", "device_idle_pct.fanout")]:
        read = harness.load_metric(name).read
        assert read(_ctx(cell, trace=t)) == pytest.approx(40.0)


def test_step_device_ms_divides_program_time_by_steps():
    t = _trace([], modules=[("jit_train_step(1)", 0, 40 * MS),
                            ("jit_train_step(1)", 50 * MS, 40 * MS),
                            ("jit_other(2)", 90 * MS, 5 * MS)])
    read = harness.load_metric("step_device_ms.train").read
    assert read(_ctx("train.smollm-360m", trace=t,
                     window={"steps": 2})) == pytest.approx(40.0)
    assert read(_ctx("train.smollm-360m", trace=t,
                     window={"steps": 0})) is None


def test_score_device_ms_divides_program_time_by_tasks():
    t = _trace([], modules=[("jit_score(5)", 0, 10 * MS),
                            ("jit_score(6)", 20 * MS, 20 * MS)])
    read = harness.load_metric("score_device_ms.fanout").read
    assert read(_ctx("fanout.smollm-360m", trace=t,
                     window={"score_tasks": 2})) == pytest.approx(15.0)
    empty = _trace([])
    assert read(_ctx("fanout.smollm-360m", trace=empty,
                     window={"score_tasks": 2})) is None


def test_mfu_train_from_rate_and_peak():
    read = harness.load_metric("mfu.train").read
    ctx = _ctx("train.smollm-360m",
               window={"end_to_end": {"train_tokens_per_s": 10000.0}})
    conf = ctx.cell.config
    fpt = flops.per_token(conf, conf["train"]["seq_len"], training=True)
    assert read(ctx) == pytest.approx(100 * fpt * 1e4 / 197e12)
    assert 0 < read(ctx) < 100


def test_flops_per_token_smollm_by_hand():
    conf = harness.load_cell("train.smollm-360m").config
    d, L, ff, V, S = 960, 32, 2560, 49152, 2048
    per_layer = d * 15 * 64 * 2 + d * 5 * 64 * 2 + 3 * d * ff
    attn = 2 * 15 * 64 * (S + 1) / 2
    want = 3 * 2 * (L * (per_layer + attn) + d * V)
    assert flops.per_token(conf, S, training=True) == pytest.approx(want)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        tr.peaks_for("TPU v9 imaginary")


def test_quantile():
    assert harness.quantile([1, 2, 3, 4, 5], 0.95) == pytest.approx(4.8)
    assert harness.quantile([], 0.5) is None
