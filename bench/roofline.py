"""A kernel's share of its roofline, from the device trace.

The least time the chip can take for one call of a kernel is the larger of
its FLOPs over the chip's bf16 peak and its bytes over its HBM bandwidth
(``bench/peaks.json``).  ``share`` sums that time over the kernel's calls
in the traced window and divides it by the same calls' device time.  The
FLOPs and bytes of a call come from the kernel's own metric file
(``bench/metrics/<kernel>_roofline...py``), which counts them from the
cell's shapes as a lower bound of the work: only compulsory traffic and no
masked-out products, so the share cannot pass 100% unless the count or the
time is wrong.
"""
from __future__ import annotations


def share(trace, prefix: str, count, peaks: dict):
    """Percent of the roofline reached by the device operations whose name
    starts with ``prefix`` and that lie wholly inside the window (a clipped
    call would count its whole work against part of its time), over every
    chip.  ``count(op_name)`` gives the call's (flops, bytes), or None for
    a call it cannot count.  None where no operation matches, or where one
    cannot be counted."""
    ideal = spent = 0.0
    for dev in trace.devices:
        for name, t, dur in dev["ops"]:
            if (not name.startswith(prefix) or t < trace.t0
                    or t + dur > trace.t1):
                continue
            work = count(name)
            if work is None:
                return None
            flops, nbytes = work
            ideal += max(flops / peaks["bf16_flops_per_s"],
                         nbytes / peaks["hbm_bytes_per_s"])
            spent += dur * 1e-9
    return 100.0 * ideal / spent if spent > 0 else None
