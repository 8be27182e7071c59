"""Program spans, state marks and the executor's compile counter."""
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import (DataFlowKernel, PilotDescription, RPEXExecutor,
                        python_app, spmd_app, tracing)


@pytest.fixture()
def rpex():
    ex = RPEXExecutor(PilotDescription(n_slots=4))
    yield ex
    ex.shutdown()


def _rp_events(trace_dir):
    """(start_ns, name, stats) of every ``rp.*`` host event, in order."""
    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.start_ns, e.name, dict(e.stats))
                           for e in line.events if e.name.startswith("rp."))
    return sorted(out, key=lambda e: e[0])


def test_span_is_the_shared_null_context_without_a_profiler():
    assert tracing.span("rp.a", "task.1", slots=(0,)) is tracing.span("rp.b")
    with tracing.span("rp.a", "task.1") as sp:
        assert sp is None
    tracing.mark("rp.state", "task.1", "DONE")       # a no-op, no error


def test_chain_leaves_spans_and_state_marks_with_uids(rpex, tmp_path):
    @python_app
    def first(x):
        return x + 1

    @spmd_app(slots=1)
    def middle(mesh, x):
        return jnp.ones(4) * x

    @python_app
    def last(y):
        return float(y.sum())

    with DataFlowKernel(executors={"rpex": rpex}):
        last(middle(first(0))).result()               # compiles outside
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            fa = first(1)
            fb = middle(fa)
            fc = last(fb)
            assert fc.result() == 8.0
        finally:
            jax.profiler.stop_trace()
    a, b, c = fa.uid, fb.uid, fc.uid
    evs = _rp_events(tmp_path)

    def spans(name):
        return [st for _, n, st in evs if n == name]

    resolves = {st["uid"]: st for st in spans("rp.dfk.resolve")}
    assert resolves[b]["cause"] == a and resolves[b]["ready"] == 1
    assert resolves[c]["cause"] == b and resolves[c]["ready"] == 1
    for name in ("rp.agent.run", "rp.executor.dispatch", "rp.agent.finish"):
        assert {a, b, c} <= {st["uid"] for st in spans(name)}, name
    # only the device task leaves arrays to wait for
    assert [st["uid"] for st in spans("rp.executor.device_wait")] == [b]
    # a submit whose inputs are ready hands the pilot task over at once;
    # the others wait on their inputs, under their DAG node's uid
    submits = [st["uid"] for st in spans("rp.dfk.submit")]
    assert len(submits) == 3 and submits[0] == a
    assert all(u.startswith("dfk.") for u in submits[1:])
    states = {}
    for _, n, st in evs:
        if n == "rp.state":
            states.setdefault(st["uid"], []).append(st["state"])
    for uid, fut in ((a, fa), (b, fb), (c, fc)):
        assert states[uid] == ["NEW", "TRANSLATED", "SCHEDULED",
                               "LAUNCHING", "RUNNING", "DONE"]
        stamps = fut.task.timestamps
        assert sorted(stamps, key=stamps.get) == states[uid]


def test_compile_counter_counts_backend_compiles(rpex):
    @spmd_app(slots=1)
    def scale(mesh, x):
        return x * 3.0

    stats = rpex.pilot.executor.stats
    with DataFlowKernel(executors={"rpex": rpex}):
        scale(jnp.ones(5)).result()
        first = dict(stats)
        scale(jnp.ones(5)).result()                   # a repeat
        repeat = dict(stats)
        scale(jnp.ones(7)).result()                   # a new shape
        new = dict(stats)
    assert first["compiles"] >= 1
    assert repeat["compiles"] == first["compiles"]
    assert new["compiles"] >= repeat["compiles"] + 1
    # one wrapper per (function, sub-mesh), whatever the shapes
    assert first["specializations"] == repeat["specializations"] == \
        new["specializations"] == 1
    assert new["cache_hits"] == 2


def test_compiles_outside_execute_are_not_counted(rpex):
    before = rpex.pilot.executor.stats["compiles"]
    jax.jit(lambda x: x - 11.0)(jnp.ones(3)).block_until_ready()
    assert rpex.pilot.executor.stats["compiles"] == before


SCOPE = re.compile(r"(?:^|[/(])(embed|attention|ssd|mlp|loss)(?=$|[/)])")


def _scoped_op_names(lowered) -> dict:
    """{scope: op_names under it} over the compiled HLO's op metadata; a
    scope reads as a path component, or inside ``jvp(...)`` and
    ``transpose(jvp(...))`` where autodiff wrapped it."""
    out = {}
    for name in re.findall(r'op_name="([^"]*)"',
                           lowered.compile().as_text()):
        for scope in SCOPE.findall(name):
            out.setdefault(scope, []).append(name)
    return out


def test_smollm_train_step_hlo_carries_layer_scopes():
    from repro.configs import get_config, reduce_config
    from repro.models import model as M
    from repro.models import transformer as T
    from repro.optim import AdamW

    cfg = reduce_config(get_config("smollm-360m"))
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32),
             "targets": jnp.zeros((2, 16), jnp.int32),
             "loss_mask": jnp.ones((2, 16))}
    opt = AdamW()
    scoped = _scoped_op_names(jax.jit(M.make_train_step(cfg, opt)).lower(
        params, opt.init(params), batch))
    assert set(scoped) == {"embed", "attention", "mlp", "loss"}
    # the backward keeps each scope under transpose(jvp(...))
    for scope, names in scoped.items():
        assert any(n.startswith("jit(train_step)/transpose(")
                   for n in names), scope


def test_mamba2_forward_hlo_carries_the_ssd_scope():
    from repro.configs import get_config, reduce_config
    from repro.models import model as M
    from repro.models import transformer as T

    cfg = reduce_config(get_config("mamba2-1.3b"))
    params = T.init_params(cfg, jax.random.PRNGKey(0))

    def forward(params, tokens):
        x = M.embed_inputs(cfg, params, {"tokens": tokens})
        h, _, _ = T.forward(cfg, params, x, mode="train")
        return M.lm_logits(cfg, params, h)

    scoped = _scoped_op_names(jax.jit(forward).lower(
        params, jnp.zeros((2, 16), jnp.int32)))
    assert set(scoped) == {"embed", "ssd", "loss"}
