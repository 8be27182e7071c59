"""launch_ms.fanout: median SCHEDULED -> RUNNING of the window's score
tasks, from the device pilot's StateStore stamps."""
import statistics


def launch_times_ms(timelines, uids):
    out = []
    for uid in uids:
        ts = timelines.get(uid, {})
        if "SCHEDULED" in ts and "RUNNING" in ts:
            out.append((ts["RUNNING"] - ts["SCHEDULED"]) * 1e3)
    return out


def read(ctx):
    xs = launch_times_ms(ctx.window["timelines"],
                         ctx.window.get("score_uids", ()))
    return statistics.median(xs) if xs else None
