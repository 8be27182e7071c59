"""``bench/expert_scopes.py``: which operations count under the expert
layer's scopes, and the per-score device time that its two metrics read."""
from types import SimpleNamespace

from bench import expert_scopes, harness

LOOP = "jit(score)/while/body/closed_call/checkpoint/checkpoint"


def test_innermost_scope_names_the_expert_layer():
    s = expert_scopes.scope_of
    assert s("fusion.3", f"{LOOP}/moe/jit(_take)/gather") == "moe"
    assert s("mul.2", f"{LOOP}/moe/experts/jit(silu)/mul") == "experts"
    assert s("sort.1", f"{LOOP}/moe/jit(argsort)/sort") == "moe"
    assert s("dot.4", f"{LOOP}/mlp/dot_general") == "mlp"
    assert s("fusion.9", f"{LOOP}/ssd/mul") == "ssd"
    assert s("fusion.7", "jit(score)/while/body/add") is None
    # XLA's ragged-dot kernels carry no scope: they are the experts' matmuls
    assert s("ragged-dot-none.29", "ragged-dot-none") == "experts"
    assert s("ragged-dot-metadata.9", "ragged-dot-metadata") == "experts"


def _ctx(tmp_path, monkeypatch, ops, tasks=2):
    cell = "fanout.granite-4.0-h-small"
    d = tmp_path / "trace" / cell
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(harness, "OUT", tmp_path)
    monkeypatch.setattr(expert_scopes, "scoped_ops", lambda path, n: ops)
    trace = SimpleNamespace(t0=0.0, t1=100e6, devices=[{}] * len(ops or [0]))
    return SimpleNamespace(cell=SimpleNamespace(name=cell), trace=trace,
                           window={"score_tasks": tasks})


def test_ms_per_score_sums_the_scope_inside_the_window(tmp_path, monkeypatch):
    ops = [[("moe", 0.0, 10e6), ("experts", 5e6, 20e6), ("moe", 95e6, 10e6),
            ("ssd", 30e6, 1e6)]]
    ctx = _ctx(tmp_path, monkeypatch, ops)
    assert expert_scopes.ms_per_score(ctx, "moe") == 7.5      # (10 + 5) / 2
    assert expert_scopes.ms_per_score(ctx, "experts") == 10.0
    # two chips: the average of their times
    ctx = _ctx(tmp_path / "b", monkeypatch, ops + [[("experts", 0.0, 40e6)]])
    assert expert_scopes.ms_per_score(ctx, "experts") == 15.0


def test_nothing_to_read_gives_no_value(tmp_path, monkeypatch):
    ctx = _ctx(tmp_path, monkeypatch, [[("ssd", 0.0, 1e6)]])
    assert expert_scopes.ms_per_score(ctx, "moe") is None     # no such op
    ctx.trace = None
    assert expert_scopes.ms_per_score(ctx, "moe") is None     # not traced
    ctx = _ctx(tmp_path / "b", monkeypatch, None)
    assert expert_scopes.ms_per_score(ctx, "moe") is None     # no protobuf
