"""device_idle_pct.fanout: 1 - (union of device operation intervals) /
(traced window), in percent, averaged over the chips."""


def read(ctx):
    return None if ctx.trace is None else ctx.trace.idle_pct()
