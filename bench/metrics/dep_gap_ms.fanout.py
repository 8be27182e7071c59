"""dep_gap_ms.fanout: median, over the chain edges of the window
(prepare -> score, score -> select), from the upstream task's DONE stamp to
the downstream task's first stamp in its pilot's StateStore (NEW where the
store has it; the store stamps a translated task TRANSLATED first): how
long the DataFlowKernel takes to resolve a dependency and hand the next task
to the pilots."""
import statistics


def gaps_ms(timelines, edges):
    out = []
    for up, down in edges:
        a, b = timelines.get(up, {}), timelines.get(down, {})
        first = [b[k] for k in ("NEW", "TRANSLATED") if k in b]
        if "DONE" in a and first:
            out.append((min(first) - a["DONE"]) * 1e3)
    return out


def read(ctx):
    xs = gaps_ms(ctx.window["timelines"], ctx.window.get("edges", ()))
    return statistics.median(xs) if xs else None
