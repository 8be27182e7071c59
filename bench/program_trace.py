"""The program's own trace: its ``rp.*`` spans and marks, and the layer
scopes of its device operations.

The program writes ``rp.*`` spans and marks (``repro.core.tracing``) into
the profiler trace on the host, on the clock of the device's operations,
each with the task's ``uid`` among its metadata.  Its models wrap layers in
``jax.named_scope`` (``embed``, ``attention``, ``ssd``, ``mlp``, ``loss``),
which reaches an operation's ``op_name``.  On a TPU trace that name is the
``tf_op`` stat of the operation's event metadata in the ``.xplane.pb``;
``jax.profiler.ProfileData`` does not show event metadata, so the scopes
are read from the raw protobuf (``xplane_pb2`` as TensorFlow ships it,
loaded from its file alone).  A fusion carries its root's ``op_name``;
backward and rematerialised operations keep their scope inside
``transpose(jvp(...))``.

``for_ctx(ctx)`` gives a ``Program`` for a traced run, parsed once per file
for every metric that reads it (host planes for the spans, device planes
for the scopes, each on first use); ``from_json`` one from a trimmed
fixture.  Where the run was not traced, or the program writes none of
these (a checkout from before them), the readers find nothing and the
metrics return None.
"""
from __future__ import annotations

import bisect
import functools
import heapq
import importlib.util
import os
import re
import sys
from collections import defaultdict

from bench import harness
from bench import trace as tr

PREFIX = "rp."
NONE = "none"                      # idle time with no rp.* span open
SCOPE = re.compile(r"(?:^|[/(])(embed|attention|ssd|mlp|loss)(?=$|[/):])")


def scope_of(op_name: str):
    """The innermost layer scope named in an ``op_name``, or None."""
    found = SCOPE.findall(op_name or "")
    return found[-1] if found else None


# ------------------------------- loading ---------------------------------- #
def host_events(path: str) -> tuple:
    """Every ``rp.*`` event of the host planes: (name, start_ns, dur_ns,
    metadata dict of strings), by start."""
    import jax
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.append((e.name, e.start_ns, e.duration_ns,
                                {k: str(v) for k, v in e.stats}))
    out.sort(key=lambda e: e[1])
    return tuple(out)


@functools.lru_cache(maxsize=1)
def _xplane_pb2():
    """TensorFlow's ``tsl/profiler/protobuf/xplane_pb2``, loaded from its
    file without importing TensorFlow (the ``bench`` extra); None, said
    once on stderr, where it is not installed."""
    spec = importlib.util.find_spec("tensorflow")
    path = spec and spec.submodule_search_locations and os.path.join(
        list(spec.submodule_search_locations)[0], "tsl", "profiler",
        "protobuf", "xplane_pb2.py")
    if not path or not os.path.isfile(path):
        print("bench: no tensorflow/tsl/profiler/protobuf/xplane_pb2.py "
              "(pip install .[bench]); the layer-scope metrics read "
              "nothing", file=sys.stderr)
        return None
    mod_spec = importlib.util.spec_from_file_location("bench_xplane_pb2",
                                                      path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def scoped_ops(path: str, devices: int):
    """Per device (the first ``devices`` TPU planes, as ``trace.load``
    takes them), the operations in a layer scope: [(scope, start_ns,
    dur_ns)].  None where the protobuf module is missing."""
    pb = _xplane_pb2()
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
            if tr.op_name(meta.name) is None:     # a loop: its body counts
                continue
            name = next((s.str_value or plane.stat_metadata[s.ref_value].name
                         for s in meta.stats if s.metadata_id == tf_op), "")
            s = scope_of(name)
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


# ------------------------------- program ---------------------------------- #
class Program:
    """The ``rp.*`` host events and the scoped device operations of one
    trace, read against that trace's window and device busy time.  Each
    is given as a function, called on first use."""

    def __init__(self, trace, events, scoped):
        self.trace = trace              # bench.trace.Trace
        self._load_events, self._load_scoped = events, scoped

    @functools.cached_property
    def events(self) -> tuple:
        return self._load_events()

    @functools.cached_property
    def scoped(self):
        return self._load_scoped()

    def _in_window(self, t) -> bool:
        return self.trace.t0 <= t <= self.trace.t1

    def named(self, name: str) -> list:
        return [e for e in self.events if e[0] == name]

    # --------------------------- agent and executor ---------------------- #
    def slot_turnarounds_ms(self, uids) -> list:
        """For each task of ``uids`` already queued at its agent when the
        previous of them on the same slots ended its
        ``rp.executor.device_wait``: from that end to the start of this
        task's ``rp.executor.dispatch``, for dispatches in the window.

        Queued is the pilot task's TRANSLATED mark (routed to a pilot and
        handed to its agent).  Its SCHEDULED mark is the slot allocation
        itself, which on a slot of its own comes only after the previous
        task has released the slot."""
        uids = set(uids)
        queued, dispatch, wait_end = {}, {}, {}
        for name, t, d, meta in self.events:
            uid = meta.get("uid")
            if uid not in uids:
                continue
            if name == "rp.state" and meta.get("state") == "TRANSLATED":
                queued.setdefault(uid, t)
            elif name == "rp.executor.dispatch":
                dispatch.setdefault(uid, (t, meta.get("slots")))
            elif name == "rp.executor.device_wait":
                wait_end.setdefault(uid, t + d)
        by_slots = defaultdict(list)
        for uid, (t, slots) in dispatch.items():
            if self._in_window(t):
                by_slots[slots].append((t, uid))
        out = []
        for runs in by_slots.values():
            runs.sort()
            for (_, prev), (t, uid) in zip(runs, runs[1:]):
                end = wait_end.get(prev)
                if (end is not None and uid in queued
                        and queued[uid] <= end <= t):
                    out.append((t - end) * 1e-6)
        return out

    def device_waits_ms(self) -> list:
        """Every ``rp.executor.device_wait`` that starts in the window."""
        return [d * 1e-6 for _, t, d, _ in
                self.named("rp.executor.device_wait") if self._in_window(t)]

    def module_ends_in_wait(self, prefix: str) -> tuple:
        """(programs ``prefix*`` run in the window, those of them that end
        inside the ``rp.executor.device_wait`` of a task dispatched before
        they ended): whether the host's and the device's clocks agree.
        The program's start is not compared with its dispatch: the two
        are a fraction of a millisecond apart, within the clocks' jitter."""
        began = {m.get("uid"): t for _, t, _, m in
                 self.named("rp.executor.dispatch")}
        waits = sorted((t, t + d, began.get(m.get("uid")))
                       for _, t, d, m in self.named("rp.executor.device_wait"))
        starts = [w[0] for w in waits]
        longest = max((b - a for a, b, _ in waits), default=0.0)
        n = inside = 0
        for dev in self.trace.devices:
            for name, s, d in dev["modules"]:
                end = s + d
                if not (name.startswith(prefix) and self._in_window(s)
                        and self._in_window(end)):
                    continue
                n += 1
                i = bisect.bisect_right(starts, end) - 1
                while i >= 0 and starts[i] >= end - longest:
                    _, b, t = waits[i]
                    if end <= b and t is not None and t <= end:
                        inside += 1
                        break
                    i -= 1
        return n, inside

    # --------------------------------- device ---------------------------- #
    def idle_by_span(self) -> dict:
        """Seconds of the device's idle time in the window, by the innermost
        (shortest) ``rp.*`` span open at the time on any thread; ``none``
        where no span is open.  Averaged over the chips."""
        spans = [(t, t + d, name) for name, t, d, _ in self.events if d > 0]
        out = defaultdict(float)
        n = len(self.trace.devices)
        for i in range(n):
            for name, ns in _idle_split(self._idle(i), spans).items():
                out[name] += ns * 1e-9 / n
        return dict(out)

    def _idle(self, dev: int) -> list:
        idle, prev = [], self.trace.t0
        for s, e in self.trace.busy_intervals(dev) + [[self.trace.t1] * 2]:
            if s > prev:
                idle.append((prev, s))
            prev = max(prev, e)
        return idle

    def scope_device_s(self, scope: str):
        """Seconds of device operations in ``scope`` in the window, averaged
        over the chips; None where the scopes could not be read."""
        scoped = self.scoped
        if not scoped:
            return None
        tot = 0.0
        for ops in scoped:
            for s, t, d in ops:
                if s == scope:
                    a, b = max(t, self.trace.t0), min(t + d, self.trace.t1)
                    tot += max(b - a, 0.0)
        return tot / len(scoped) * 1e-9


def _idle_split(idle: list, spans: list) -> dict:
    """Nanoseconds of the ``idle`` intervals (sorted, disjoint) by the
    shortest of ``spans`` (start, end, name) open at each instant, or
    ``none``: one sweep over the spans' boundaries."""
    bounds = sorted([(a, 1, i) for i, (a, _, _) in enumerate(spans)]
                    + [(b, 0, i) for i, (_, b, _) in enumerate(spans)])
    out = defaultdict(float)
    open_, heap, j = set(), [], 0

    def advance(t):
        nonlocal j
        while j < len(bounds) and bounds[j][0] <= t:
            _, opens, i = bounds[j]
            if opens:
                open_.add(i)
                heapq.heappush(heap, (spans[i][1] - spans[i][0], i))
            else:
                open_.discard(i)
            j += 1

    for a, b in idle:
        advance(a)
        t = a
        while t < b:
            nxt = min(bounds[j][0] if j < len(bounds) else b, b)
            while heap and heap[0][1] not in open_:
                heapq.heappop(heap)
            out[spans[heap[0][1]][2] if heap else NONE] += nxt - t
            t = nxt
            advance(t)
    return out


# ------------------------------ entry points ------------------------------ #
def for_ctx(ctx):
    """The ``Program`` of a traced run's cell (one for all the metrics of
    the run), or None where it was not traced or left no trace file."""
    if ctx.trace is None:
        return None
    try:
        path = tr.find_xplane(harness.OUT / "trace" / ctx.cell.name)
    except FileNotFoundError:
        return None
    return _program(path, ctx.trace)


@functools.lru_cache(maxsize=2)
def _program(path: str, trace) -> Program:
    return Program(trace, lambda: host_events(path),
                   lambda: scoped_ops(path, len(trace.devices)))


def from_json(obj: dict) -> Program:
    """A ``Program`` from a trimmed trace kept as a test fixture:
    ``trace.from_json``'s keys, ``rp`` (host events) and ``scoped``."""
    events = tuple((n, t, d, dict(m)) for n, t, d, m in obj.get("rp", ()))
    scoped = [[tuple(o) for o in ops] for ops in obj.get("scoped", ())]
    return Program(tr.from_json(obj), lambda: events, lambda: scoped)
