"""idle_unclaimed_pct.fanout: the share of the device's idle time in the
window during which no ``rp.*`` span is open on any thread, in percent:
idle that the program's own spans do not account for."""
from bench import program_trace


def read(ctx):
    prog = program_trace.for_ctx(ctx)
    if prog is None or not prog.events:
        return None
    idle = prog.idle_by_span()
    total = sum(idle.values())
    return 100.0 * idle.get(program_trace.NONE, 0.0) / total if total else None
