"""moe_device_ms.fanout: device time of the operations whose innermost
scope is ``moe`` (the expert layer's router, top-k, sort, gathers and
combine, the grouped matmuls aside) in the traced window, per score task
completed in it (``bench/expert_scopes.py``)."""
from bench import expert_scopes


def read(ctx):
    return expert_scopes.ms_per_score(ctx, "moe")
