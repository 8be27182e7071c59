"""mlp_device_ms.train: device time of the operations in the ``mlp``
scope (``jax.named_scope`` in the models; forward, backward and
rematerialised) in the traced window, per train step run in it."""
from bench import program_trace


def read(ctx):
    prog = program_trace.for_ctx(ctx)
    steps = ctx.window.get("steps")
    t = prog.scope_device_s("mlp") if prog is not None and steps else None
    return t / steps * 1e3 if t else None
