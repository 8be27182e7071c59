"""ssd_device_ms.fanout: device time of the operations in the ``ssd`` scope
(the Mamba2 mixer's chunked scan) in the traced window, per score task
completed in it."""
from bench import program_trace


def read(ctx):
    prog = program_trace.for_ctx(ctx)
    n = ctx.window.get("score_tasks")
    t = prog.scope_device_s("ssd") if prog is not None and n else None
    return t / n * 1e3 if t else None
