"""device_wait_max_ms.fanout: the longest ``rp.executor.device_wait`` (the
executor's ``jax.block_until_ready`` on a task's result) that starts in the
window: about one score program in a clean run, seconds in a stall."""
from bench import program_trace


def read(ctx):
    prog = program_trace.for_ctx(ctx)
    waits = prog.device_waits_ms() if prog is not None else ()
    return max(waits) if waits else None
