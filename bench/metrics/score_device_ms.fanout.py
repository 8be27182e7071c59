"""score_device_ms.fanout: device time of the score program
(``jit_score``) in the traced window, per score task completed in it."""

PROGRAM = "jit_score"


def read(ctx):
    n = ctx.window.get("score_tasks")
    if not n or ctx.trace is None:
        return None
    t = ctx.trace.module_s(PROGRAM)
    return t / n * 1e3 if t > 0 else None
