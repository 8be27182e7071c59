"""Device time of the expert layer's operations, by their innermost scope:
``moe`` (router, top-k, sort, gathers, combine) or ``experts`` (the grouped
matmuls), as ``repro.models.moe`` names them.

Read from a traced run's ``.xplane.pb`` the way ``program_trace.scoped_ops``
reads the other layer scopes, over those scopes and the expert layer's two,
so that an operation counts under the innermost of all of them.  XLA's TPU
expansion of ``jax.lax.ragged_dot`` names its kernels ``ragged-dot-*`` and
gives them no scope; the grouped matmuls of ``experts`` are the program's
only ragged dots, so those operations count there.
"""
from __future__ import annotations

import functools
import re

from bench import harness, program_trace
from bench import trace as tr

SCOPE = re.compile(r"(?:^|[/(])(embed|attention|ssd|mlp|loss|moe|experts)"
                   r"(?=$|[/):])")
RAGGED_DOT = "ragged-dot"


def scope_of(hlo_name: str, op_name: str):
    if hlo_name.startswith(RAGGED_DOT):
        return "experts"
    found = SCOPE.findall(op_name or "")
    return found[-1] if found else None


@functools.lru_cache(maxsize=1)
def scoped_ops(path: str, devices: int):
    """Per device, [(scope, start_ns, dur_ns)] of the operations in a scope;
    None where the protobuf module is missing."""
    pb = program_trace._xplane_pb2()
    if pb is None:
        return None
    space = pb.XSpace()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    out = []
    for plane in space.planes:
        if not plane.name.startswith("/device:TPU:") or len(out) >= devices:
            continue
        tf_op = next((k for k, m in plane.stat_metadata.items()
                      if m.name == "tf_op"), None)
        scope = {}
        for mid, meta in plane.event_metadata.items():
            hlo = tr.op_name(meta.name)
            if hlo is None:                           # a loop: its body counts
                continue
            name = next((s.str_value or plane.stat_metadata[s.ref_value].name
                         for s in meta.stats if s.metadata_id == tf_op), "")
            s = scope_of(hlo, name)
            if s is not None:
                scope[mid] = s
        ops = []
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            t0 = line.timestamp_ns
            for e in line.events:
                s = scope.get(e.metadata_id)
                if s is not None:
                    ops.append((s, t0 + e.offset_ps / 1000.0,
                                e.duration_ps / 1000.0))
        out.append(ops)
    return out


def ms_per_score(ctx, scope: str):
    """Device milliseconds of ``scope``'s operations in the traced window,
    averaged over the chips, per score task completed in it; None where the
    run was not traced, the scopes could not be read or none ran."""
    n = ctx.window.get("score_tasks")
    if ctx.trace is None or not n:
        return None
    try:
        path = tr.find_xplane(harness.OUT / "trace" / ctx.cell.name)
    except FileNotFoundError:
        return None
    ops = scoped_ops(path, len(ctx.trace.devices))
    if not ops:
        return None
    t0, t1 = ctx.trace.t0, ctx.trace.t1
    tot = sum(max(min(t + d, t1) - max(t, t0), 0.0)
              for dev in ops for s, t, d in dev if s == scope)
    return tot / len(ops) * 1e-6 / n if tot else None
