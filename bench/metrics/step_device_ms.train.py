"""step_device_ms.train: device time of the train-step program
(``jit_train_step``) in the traced window, per step run in it."""

PROGRAM = "jit_train_step"


def read(ctx):
    steps = ctx.window.get("steps")
    if not steps or ctx.trace is None:
        return None
    t = ctx.trace.module_s(PROGRAM)
    return t / steps * 1e3 if t > 0 else None
