"""From a profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a plain
structure: per device, its operations (``XLA Ops``) and programs
(``XLA Modules``), and the host spans the benchmark writes
(``HOST_SPANS``), all on the profiler's clock in nanoseconds.  ``Trace`` reduces it: the device's busy time (the union of its
operation intervals inside the window, averaged over the chips), the idle
share, the time of one program, the operations that took most time, and the
longest idle gaps named by the innermost host span open at the time.
``from_json`` reads a trimmed trace kept as a test fixture.
"""
from __future__ import annotations

import glob
import json
import os
import re
from collections import defaultdict
from pathlib import Path

HOST_SPANS = ("window", "submit", "await_result", "build_batch", "prepare",
              "select")
PEAKS = Path(__file__).resolve().parent / "peaks.json"
# ops that only hold others (a layer scan's loop): their children are in the
# trace, so they count neither as busy time nor as an operation of their own
CONTAINER = re.compile(r"\s(while|conditional|call)\(")


def op_name(text: str):
    """``fusion.3`` from an HLO line ``%fusion.3 = bf16[..] fusion(..)``;
    None for a container op."""
    if CONTAINER.search(text):
        return None
    return text.split(" = ", 1)[0].lstrip("%")


def peaks_for(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def find_xplane(trace_dir) -> str:
    hits = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                         "*.xplane.pb"), recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def load(path: str, devices: int) -> "Trace":
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devs, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and len(devs) < devices:
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        name = op_name(e.name)
                        if name is not None:
                            ops.append((name, e.start_ns, e.duration_ns))
                elif line.name == "XLA Modules":
                    mods.extend((e.name, e.start_ns, e.duration_ns)
                                for e in line.events)
            devs.append({"ops": ops, "modules": mods})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events if e.name in HOST_SPANS)
    return Trace(devs, host)


def _union(intervals):
    """Merged [start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class Trace:
    def __init__(self, devices, host):
        self.devices = devices          # [{"ops": [(name, t, dur)], ...}]
        self.host = host                # [(name, t, dur)]
        wins = [(t, t + d) for n, t, d in host if n == "window"]
        if not wins:
            raise ValueError("the trace has no 'window' span")
        self.t0, self.t1 = wins[0]
        self.window_s = (self.t1 - self.t0) * 1e-9

    # ---------------------------- reductions ---------------------------- #
    def _clip(self, events):
        for name, t, d in events:
            s, e = max(t, self.t0), min(t + d, self.t1)
            if e > s:
                yield name, s, e

    def busy_intervals(self, dev: int):
        return _union([s, e] for _, s, e in
                      self._clip(self.devices[dev]["ops"]))

    def busy_s(self) -> float:
        """Seconds with an operation running on the device, in the window,
        averaged over the chips."""
        tot = [sum(e - s for s, e in self.busy_intervals(i))
               for i in range(len(self.devices))]
        return sum(tot) / len(tot) * 1e-9 if tot else 0.0

    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s() / self.window_s)

    def module_s(self, prefix: str) -> float:
        """Seconds of the programs whose name starts with ``prefix``, in the
        window, averaged over the chips."""
        tot = [sum(e - s for n, s, e in self._clip(d["modules"])
                   if n.startswith(prefix)) for d in self.devices]
        return sum(tot) / len(tot) * 1e-9 if tot else 0.0

    def top_ops(self, n: int = 10):
        acc = defaultdict(float)
        for d in self.devices:
            for name, s, e in self._clip(d["ops"]):
                acc[name] += (e - s) * 1e-9 / len(self.devices)
        return sorted(([k, v] for k, v in acc.items()),
                      key=lambda kv: -kv[1])[:n]

    def host_span_at(self, t: float) -> str:
        """The innermost benchmark span open at ``t`` (other than the
        window), or ``none``."""
        best = None
        for name, s, d in self.host:
            if name != "window" and s <= t < s + d:
                if best is None or d < best[1]:
                    best = (name, d)
        return best[0] if best else "none"

    def idle_gaps(self, n: int = 10):
        gaps = []
        for i in range(len(self.devices)):
            prev = self.t0
            for s, e in self.busy_intervals(i) + [[self.t1, self.t1]]:
                if s > prev:
                    gaps.append((s - prev, prev, s))
                prev = max(prev, e)
        gaps.sort(reverse=True)
        return [[self.host_span_at((a + b) / 2), g * 1e-9]
                for g, a, b in gaps[:n]]

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def from_json(obj: dict) -> Trace:
    devs = [{k: [tuple(e) for e in v] for k, v in d.items()}
            for d in obj["devices"]]
    return Trace(devs, [tuple(e) for e in obj["host"]])
