"""The reader of the program's own spans, marks and layer scopes, and the
per-layer metrics built on it: hand-made events, then trimmed traces
recorded on the chip."""
import gzip
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import harness, program_trace as pt, trace as tr

MS = 1e6
DATA = Path(__file__).resolve().parent / "data"


def _prog(events=(), ops=(), modules=(), scoped=None, window=(0, 100 * MS)):
    trace = tr.Trace([{"ops": list(ops), "modules": list(modules)}],
                     [("window", window[0], window[1] - window[0])])
    events = tuple(sorted(events, key=lambda e: e[1]))
    return pt.Program(trace, lambda: events, lambda: scoped)


def _read(metric, cell, prog, window, monkeypatch):
    monkeypatch.setattr(pt, "for_ctx", lambda ctx: prog)
    ctx = SimpleNamespace(cell=harness.load_cell(cell), window=window,
                          trace=None if prog is None else prog.trace)
    return harness.load_metric(metric).read(ctx)


def _task(uid, queued, dispatch, wait, slots="0"):
    """A score task's marks and spans: queued at its agent, dispatched, and
    waited on (start, length)."""
    return [("rp.state", queued, 0, {"uid": uid, "state": "TRANSLATED"}),
            ("rp.executor.dispatch", dispatch, 1 * MS,
             {"uid": uid, "slots": slots}),
            ("rp.executor.device_wait", wait[0], wait[1],
             {"uid": uid, "slots": slots})]


@pytest.mark.parametrize("op_name,scope", [
    ("jit(train_step)/jvp(embed)/jit(_take)/gather:", "embed"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/attention/mul", "attention"),
    ("jit(train_step)/transpose(jvp(loss))/select_n", "loss"),
    ("jit(score)/while/body/ssd/exp:", "ssd"),
    ("jit(train_step)/jvp(loss)/loss/dot_general", "loss"),
    ("jit(train_step)/attention/mlp_like/mlp/dot", "mlp"),
    ("jit(train_step)/add", None),
    ("jit(f)/embedding/mlps/add", None),
    ("", None)])
def test_scope_of_reads_the_innermost_scope(op_name, scope):
    assert pt.scope_of(op_name) == scope


def test_slot_turnaround_counts_only_tasks_already_queued(monkeypatch):
    events = (_task("a", 1 * MS, 2 * MS, (4 * MS, 10 * MS))
              # queued before a's wait ended: 2 ms from 14 to 16
              + _task("b", 5 * MS, 16 * MS, (18 * MS, 10 * MS))
              # queued after b's wait ended: the slot had no work, left out
              + _task("c", 40 * MS, 41 * MS, (43 * MS, 10 * MS))
              # queued in time, 4 ms after c's wait
              + _task("d", 50 * MS, 57 * MS, (59 * MS, 10 * MS))
              # another slot, no predecessor there
              + _task("e", 0, 30 * MS, (31 * MS, 5 * MS), slots="1"))
    prog = _prog(events)
    assert prog.slot_turnarounds_ms("abcde") == pytest.approx([2.0, 4.0])
    win = {"score_uids": list("abcde")}
    assert _read("slot_turnaround_ms.fanout", "fanout.smollm-360m", prog,
                 win, monkeypatch) == pytest.approx(3.0)
    # tasks that are not the window's score tasks are not read
    assert _read("slot_turnaround_ms.fanout", "fanout.smollm-360m", prog,
                 {"score_uids": ["x"]}, monkeypatch) is None


def test_device_wait_max_is_the_longest_in_the_window(monkeypatch):
    events = (_task("a", 0, 1 * MS, (2 * MS, 15 * MS))
              + _task("b", 0, 20 * MS, (21 * MS, 60 * MS))
              + _task("c", 0, 95 * MS, (101 * MS, 90 * MS)))   # after it
    prog = _prog(events)
    assert _read("device_wait_max_ms.fanout", "fanout.mamba2-1.3b", prog,
                 {}, monkeypatch) == pytest.approx(60.0)


def test_idle_is_split_by_the_innermost_open_span():
    ops = [("op", 0, 10 * MS), ("op", 30 * MS, 20 * MS),
           ("op", 90 * MS, 10 * MS)]
    events = [("rp.agent.finish", 5 * MS, 40 * MS, {"uid": "a"}),
              ("rp.dfk.resolve", 12 * MS, 6 * MS, {"uid": "b"}),
              # another thread: a longer span open across the second gap
              ("rp.agent.run", 45 * MS, 40 * MS, {"uid": "c"}),
              ("rp.state", 60 * MS, 0, {"uid": "c", "state": "DONE"})]
    prog = _prog(events, ops=ops)
    idle = prog.idle_by_span()
    # gap 10-30: resolve 12-18, finish the rest; gap 50-90: run 50-85
    assert idle == pytest.approx({"rp.dfk.resolve": 0.006,
                                  "rp.agent.finish": 0.014,
                                  "rp.agent.run": 0.035, pt.NONE: 0.005})
    assert sum(idle.values()) == pytest.approx(prog.trace.window_s
                                               - prog.trace.busy_s())


def test_idle_unclaimed_pct(monkeypatch):
    ops = [("op", 0, 50 * MS)]
    events = [("rp.agent.run", 50 * MS, 30 * MS, {"uid": "a"})]
    prog = _prog(events, ops=ops)
    assert _read("idle_unclaimed_pct.fanout", "fanout.smollm-360m", prog,
                 {}, monkeypatch) == pytest.approx(40.0)


def test_clocks_agree_where_programs_end_inside_their_wait():
    modules = [("jit_score(1)", 3 * MS, 10 * MS),     # ends in a's wait
               ("jit_score(2)", 22 * MS, 30 * MS),    # ends after b's
               # starts a little before c's dispatch, ends in c's wait
               ("jit_score(1)", 59.9 * MS, 8 * MS),
               ("jit_other(3)", 60 * MS, 1 * MS),
               # ends in d's wait, but before d was dispatched
               ("jit_score(1)", 80 * MS, 5 * MS),
               ("jit_score(1)", 95 * MS, 10 * MS)]    # ends after the window
    events = (_task("a", 0, 1 * MS, (4 * MS, 10 * MS))
              + _task("b", 0, 20 * MS, (21 * MS, 10 * MS))
              + _task("c", 0, 60 * MS, (61 * MS, 9 * MS))
              + _task("d", 0, 86 * MS, (84 * MS, 6 * MS)))
    assert _prog(events, modules=modules).module_ends_in_wait(
        "jit_score") == (4, 2)


def test_scope_metrics_divide_scope_time_by_work(monkeypatch):
    scoped = [[("attention", 0, 30 * MS), ("mlp", 30 * MS, 20 * MS),
               ("loss", 50 * MS, 10 * MS), ("attention", 95 * MS, 10 * MS)]]
    prog = _prog(scoped=scoped)
    for metric, want in [("attention_device_ms.train", 17.5),
                         ("mlp_device_ms.train", 10.0),
                         ("loss_device_ms.train", 5.0)]:
        assert _read(metric, "train.smollm-360m", prog, {"steps": 2},
                     monkeypatch) == pytest.approx(want), metric
    ssd = _prog(scoped=[[("ssd", 0, 12 * MS)]])
    assert _read("ssd_device_ms.fanout", "fanout.mamba2-1.3b", ssd,
                 {"score_tasks": 3}, monkeypatch) == pytest.approx(4.0)


@pytest.mark.parametrize("metric,cell", [
    ("slot_turnaround_ms.fanout", "fanout.smollm-360m"),
    ("device_wait_max_ms.fanout", "fanout.smollm-360m"),
    ("idle_unclaimed_pct.fanout", "fanout.mamba2-1.3b"),
    ("attention_device_ms.train", "train.smollm-360m"),
    ("mlp_device_ms.train", "train.smollm-360m"),
    ("loss_device_ms.train", "train.smollm-360m"),
    ("ssd_device_ms.fanout", "fanout.mamba2-1.3b")])
def test_a_program_without_spans_or_scopes_reads_nothing(metric, cell,
                                                         monkeypatch):
    """A checkout from before the spans and scopes: no value, no error."""
    window = {"score_uids": ["a"], "steps": 2, "score_tasks": 2}
    for prog in (_prog(ops=[("op", 0, 10 * MS)], scoped=[[]]),
                 _prog(ops=[("op", 0, 10 * MS)], scoped=None), None):
        assert _read(metric, cell, prog, window, monkeypatch) is None


def test_untraced_run_has_no_program():
    ctx = SimpleNamespace(cell=harness.load_cell("fanout.smollm-360m"),
                          trace=None, window={})
    assert pt.for_ctx(ctx) is None


def test_xplane_reader_on_a_cpu_trace(tmp_path):
    """The host spans through ``jax.profiler.ProfileData``, the device scopes
    through the raw protobuf: a CPU trace has the first and no TPU plane."""
    import jax
    from repro.core import tracing
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with tracing.span("rp.executor.dispatch", "task.7", slots=(0, 1)):
        tracing.mark("rp.state", "task.7", "RUNNING")
    jax.profiler.stop_trace()
    path = tr.find_xplane(tmp_path)
    names = [(n, m) for n, _, _, m in pt.host_events(path)]
    assert names == [("rp.executor.dispatch",
                      {"uid": "task.7", "slots": "0 1"}),
                     ("rp.state", {"uid": "task.7", "state": "RUNNING"})]
    assert pt.scoped_ops(path, 1) == []


def test_missing_xplane_module_is_said_not_silent(tmp_path, monkeypatch,
                                                  capsys):
    """Without TensorFlow's protobuf module the scopes read None, and the
    run says why on stderr, so a scope metric left out of the result line
    is not taken for a program that has no scopes."""
    monkeypatch.setattr(pt.importlib.util, "find_spec", lambda name: None)
    pt._xplane_pb2.cache_clear()
    try:
        assert pt.scoped_ops(str(tmp_path / "absent.xplane.pb"), 1) is None
    finally:
        pt._xplane_pb2.cache_clear()
    assert "layer-scope metrics read nothing" in capsys.readouterr().err


# ------------------------- traces recorded on the chip -------------------- #
def _recorded(name):
    with gzip.open(DATA / name, "rt") as fh:
        obj = json.load(fh)
    return pt.from_json(obj), obj


@pytest.mark.parametrize("name", ["trace_program_fanout_smollm.json.gz",
                                  "trace_program_fanout_mamba.json.gz"])
def test_recorded_clocks_agree(name):
    """Every ``jit_score`` program of the slice ends inside the device wait
    of its task: the host's spans and the device's operations share a
    clock."""
    prog, _ = _recorded(name)
    n, inside = prog.module_ends_in_wait("jit_score")
    assert n >= 9 and inside == n


def test_recorded_fanout_smollm(monkeypatch):
    """0.25 s of a traced `fanout.smollm-360m` window on a v5e: the values
    as read when it was recorded, within their predicted ranges."""
    prog, obj = _recorded("trace_program_fanout_smollm.json.gz")
    win = {"score_uids": obj["score_uids"]}
    cell = "fanout.smollm-360m"
    turn = _read("slot_turnaround_ms.fanout", cell, prog, win, monkeypatch)
    assert 0.5 < turn < 3.5                       # read 0.87
    wait = _read("device_wait_max_ms.fanout", cell, prog, win, monkeypatch)
    assert 37.45 < wait < 45                      # the 1024-token score
    unclaimed = _read("idle_unclaimed_pct.fanout", cell, prog, win,
                      monkeypatch)
    assert 0 < unclaimed < 25                     # read 4.6
    idle = prog.idle_by_span()
    assert sum(idle.values()) == pytest.approx(
        prog.trace.window_s - prog.trace.busy_s())
    assert max(idle, key=idle.get) == "rp.executor.device_wait"
    assert set(idle) <= {pt.NONE} | {n for n, _, _, _ in prog.events}


def test_recorded_fanout_mamba_ssd(monkeypatch):
    prog, obj = _recorded("trace_program_fanout_mamba.json.gz")
    tasks = len(prog.trace.devices[0]["modules"])
    ssd = _read("ssd_device_ms.fanout", "fanout.mamba2-1.3b", prog,
                {"score_tasks": tasks}, monkeypatch)
    assert 8 < ssd < 14                           # read 10.8 ms a task
    assert prog.scope_device_s("attention") == 0


def test_recorded_train_scopes_hold_the_step():
    """0.6 s of a traced `train.smollm-360m` step: the attention, MLP and
    loss scopes hold at least 85% of the step program's device time."""
    prog, _ = _recorded("trace_program_train_smollm.json.gz")
    step = prog.trace.module_s("jit_train_step")
    scoped = sum(prog.scope_device_s(s) for s in ("attention", "mlp", "loss"))
    assert step > 0.5 and scoped >= 0.85 * step   # read 97%
    assert prog.scope_device_s("attention") > prog.scope_device_s("mlp")
