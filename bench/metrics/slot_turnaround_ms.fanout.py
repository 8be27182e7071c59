"""slot_turnaround_ms.fanout: median, over the window's score tasks that
were already queued at the device pilot's agent (their TRANSLATED
``rp.state`` mark) when the previous score task on the same slots ended its
``rp.executor.device_wait``, of the time from that end to the start of the
task's ``rp.executor.dispatch``: the host work between two device tasks on
a slot (finish, schedule, launch)."""
import statistics

from bench import program_trace


def read(ctx):
    prog = program_trace.for_ctx(ctx)
    xs = [] if prog is None else prog.slot_turnarounds_ms(
        ctx.window.get("score_uids", ()))
    return statistics.median(xs) if xs else None
