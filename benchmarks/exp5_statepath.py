"""Experiment 5: per-task bookkeeping overhead + wide fan-in launch latency.

PR 1/2 removed polling from the submit->schedule->complete spine; what was
left on the critical path was bookkeeping: a synchronous ``json.dumps`` +
line-buffered disk write under the StateStore lock per state transition, a
linear scan per restart lookup, and per-future dependency callbacks plus a
fresh ``threading.Timer`` per bulk window in the DFK.  PR 3 made the state
path write-behind (group commit), indexed (O(1) ``completed_result``) and
batched (one dependency-manager pass + one persistent flusher).  This
experiment measures all three against faithful reimplementations of the
PR-2 baselines:

  * ``record``   — per-task journal bookkeeping cost on the stream path
                   (full 6-transition lifecycle per task, drained to disk);
  * ``lookup``   — ``completed_result`` latency at restart scale;
  * ``fanin``    — N producers -> 1 consumer: latency from the last
                   producer completing to the aggregated consumer result;
  * ``fanout``   — 1 producer -> N consumers: producer completion to the
                   last consumer result (single-pass batch launch).

Emits ``BENCH_statepath.json`` at the repo root.  ``--min-speedup`` gates
the journaled record path (CI requires >= 2x) and ``--min-fanin-speedup``
gates the fan-in launch latency (CI requires >= 3x).
"""
from __future__ import annotations

import argparse
import json
import shutil
import tempfile
import threading
import time
from pathlib import Path

from repro.core import (EVENTS, DataFlowKernel, PilotDescription,
                        RPEXExecutor, TaskRecord, TaskState)
from repro.core.dfk import _find_futures, _resolve
from repro.core.executors import ParslTask


# ------------------- PR-2 baseline: synchronous journal ------------------- #

def _jsonable(x) -> bool:
    """PR-2's serializability probe — itself a dumps, paid per DONE record
    on the caller's thread."""
    try:
        json.dumps(x)
        return True
    except (TypeError, ValueError):
        return False


class SyncStateStore:
    """The PR-2 StateStore write path, kept for comparison: every record
    does json.dumps + a line-buffered write (one syscall per line) while
    holding the store lock, and completed_result scans every record."""

    def __init__(self, journal_path: str):
        self.journal_path = Path(journal_path)
        self._lock = threading.Lock()
        self.tasks = {}
        self.events = []
        self.journal_path.parent.mkdir(parents=True, exist_ok=True)
        self._fh = open(self.journal_path, "a", buffering=1)

    def record(self, task: TaskRecord, workflow_key=None):
        rec = {"uid": task.uid, "key": workflow_key, "kind": task.kind,
               "state": task.state.value, "retries": task.retries,
               "slot_ids": list(task.slot_ids), "t": time.time()}
        if task.state == TaskState.DONE and _jsonable(task.result):
            rec["result"] = task.result
        ev = {"event": EVENTS.STATE, "uid": task.uid,
              "state": task.state.value,
              "t": time.monotonic(), "slots": len(task.slot_ids) or 1}
        with self._lock:
            prev = self.tasks.get(task.uid, {})
            if rec.get("key") is None:
                rec["key"] = prev.get("key")
            self.tasks[task.uid] = {**prev, **rec}
            self.events.append(ev)
            self._fh.write(json.dumps(self.tasks[task.uid]) + "\n")

    def completed_result(self, workflow_key: str):
        with self._lock:
            for rec in self.tasks.values():
                if rec.get("key") == workflow_key and \
                        rec.get("state") == TaskState.DONE.value and \
                        "result" in rec:
                    return True, rec["result"]
        return False, None

    def close(self):
        with self._lock:
            if self._fh:
                self._fh.close()
                self._fh = None


# ------------- PR-2 baseline: callback-chain + Timer-window DFK ------------ #

class BaselineDFK(DataFlowKernel):
    """The PR-2 dependency/flush control flow, reimplemented on today's
    kernel: one done-callback per (consumer, dependency) edge with a
    per-node lock, and a fresh threading.Timer spawned per bulk window."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self._timers = {}

    def submit(self, fn, args=(), kwargs=None, resources=None, retries=0,
               executor=None, sticky=None):
        from repro.core.futures import (AppFuture, ResourceSpec, TaskRecord,
                                        new_uid)
        kwargs = kwargs or {}
        name = getattr(fn, "__name__", "app")
        with self._lock:
            idx = self._invocation_idx.get(name, 0)
            self._invocation_idx[name] = idx + 1
        key = f"{self.run_id}/{name}:{idx}" if self.run_id else None
        node = TaskRecord(uid=new_uid("dfk"), kind="parsl", fn=fn,
                          args=args, kwargs=kwargs,
                          resources=resources or ResourceSpec())
        future = AppFuture(node)
        label = (executor or getattr(fn, "__executor__", None)
                 or self.default_executor)
        ex = self.executors[label]

        deps = [f for f in _find_futures((args, kwargs)) if not f.done()]

        def launch():
            try:
                r_args = tuple(_resolve(a) for a in args)
                r_kwargs = {k: _resolve(v) for k, v in kwargs.items()}
            except BaseException as e:
                node.transition(TaskState.FAILED)
                if not future.done():
                    future.set_exception(e)
                return
            pt = ParslTask(fn, r_args, r_kwargs, node.resources, retries,
                           key, executor=label)
            node.transition(TaskState.TRANSLATED)
            self._old_dispatch(ex, pt, future)

        if not deps:
            launch()
        else:
            remaining = [len(deps)]
            rlock = threading.Lock()

            def on_dep(_):
                with rlock:
                    remaining[0] -= 1
                    ready = remaining[0] == 0
                if ready:
                    launch()

            for d in deps:               # one callback per edge (PR-2)
                d.add_done_callback(on_dep)
        return future

    def _old_dispatch(self, ex, pt, future):
        if self.bulk and ex.supports_bulk:
            label = pt.executor or ex.label
            with self._lock:
                self._pending_bulk.setdefault(label, []).append((pt, future))
                if label not in self._timers:
                    t = threading.Timer(self.bulk_window, self.flush, [label])
                    t.daemon = True
                    self._timers[label] = t
                    t.start()
        else:
            ex.submit(pt, future)

    def flush(self, executor=None):
        with self._lock:
            labels = ([executor] if executor is not None
                      else list(self._pending_bulk))
            batches = {}
            for label in labels:
                pairs = self._pending_bulk.pop(label, [])
                if pairs:
                    batches[label] = pairs
                timer = self._timers.pop(label, None)
                if timer is not None:
                    timer.cancel()
        for label, pairs in batches.items():
            self.executors[label].submit_bulk(pairs)


# ------------------------------ measurements ------------------------------ #

def _lifecycle(store, uid, key, result):
    t = TaskRecord(uid=uid, kind="python")
    for st in (TaskState.TRANSLATED, TaskState.SCHEDULED,
               TaskState.LAUNCHING, TaskState.RUNNING):
        t.state = st
        store.record(t, workflow_key=key)
    t.result = result
    t.state = TaskState.DONE
    store.record(t, workflow_key=key)


def bench_record(store_factory, n_tasks: int, path: str) -> float:
    """Seconds per task for a full journaled lifecycle (6 records), drained
    to disk (close() included, so write-behind pays for its queue)."""
    store = store_factory(path)
    t0 = time.monotonic()
    for i in range(n_tasks):
        _lifecycle(store, f"t{i}", f"k{i}", i)
    store.close()
    return (time.monotonic() - t0) / n_tasks


def bench_lookup(store_factory, n_records: int, n_lookups: int,
                 path: str) -> float:
    """Seconds per completed_result lookup at restart scale."""
    store = store_factory(path)
    for i in range(n_records):
        t = TaskRecord(uid=f"t{i}", kind="python")
        t.result = i
        t.state = TaskState.DONE
        store.record(t, workflow_key=f"k{i}")
    keys = [f"k{(i * 7919) % n_records}" for i in range(n_lookups)]
    t0 = time.monotonic()
    for k in keys:
        found, _ = store.completed_result(k)
        assert found
    dt = (time.monotonic() - t0) / n_lookups
    store.close()
    return dt


def _noop(x):
    return x


def _agg(xs):
    return len(xs)


def _fan_rpex(n_slots: int) -> RPEXExecutor:
    return RPEXExecutor(PilotDescription(n_slots=n_slots))


def bench_fanin(dfk_cls, n_producers: int, n_slots: int) -> dict:
    """N producers -> 1 consumer.  Launch latency = last producer
    completion -> consumer SCHEDULED on the pilot (from the unified event
    stream): the time the dependency/bookkeeping machinery takes to get
    the aggregator into the executor, excluding its execution.  The
    completion latency (-> result available) is reported alongside."""
    rpex = _fan_rpex(n_slots)
    try:
        with dfk_cls(executors={"rpex": rpex}, bulk=True) as dfk:
            # ---- fan-in: N -> 1 ----
            done_t = []
            tlock = threading.Lock()

            def stamp(_f):
                with tlock:
                    done_t.append(time.monotonic())

            prods = [dfk.submit(_noop, (i,)) for i in range(n_producers)]
            for f in prods:
                f.add_done_callback(stamp)
            agg = dfk.submit(_agg, (prods,))
            dfk.flush()
            assert agg.result(timeout=60) == n_producers
            t_agg = time.monotonic()
            tl = rpex.pilot.store.timeline()
            sched = tl[agg.task.uid]["SCHEDULED"]
            fanin_launch = sched - max(done_t)
            fanin_total = t_agg - max(done_t)

            # ---- fan-out: 1 -> N ----
            gate = threading.Event()

            def root():
                gate.wait(30)
                return 0

            froot = dfk.submit(root)
            t_root = [None]
            froot.add_done_callback(
                lambda _f: t_root.__setitem__(0, time.monotonic()))
            cons = [dfk.submit(_noop, (froot,)) for _ in range(n_producers)]
            dfk.flush()
            time.sleep(0.05)             # consumers are all registered
            gate.set()
            for f in cons:
                f.result(timeout=60)
            fanout_total = time.monotonic() - t_root[0]
            tl = rpex.pilot.store.timeline()
            # launch = every consumer routed into the executor (TRANSLATED
            # on the pilot); SCHEDULED would fold in slot-drain time when
            # the fan width exceeds the slot count
            fanout_launch = max(
                tl[f.task.uid]["TRANSLATED"] for f in cons) - t_root[0]
        return {"fanin_launch_s": fanin_launch,
                "fanin_total_s": fanin_total,
                "fanout_launch_s": fanout_launch,
                "fanout_total_s": fanout_total}
    finally:
        rpex.shutdown()


def main(argv=None):
    from repro.core import StateStore

    ap = argparse.ArgumentParser()
    ap.add_argument("--tasks", type=int, default=2000,
                    help="tasks for the record-path benchmark (6 journal "
                         "records each)")
    ap.add_argument("--records", type=int, default=20000,
                    help="store size for the lookup benchmark")
    ap.add_argument("--lookups", type=int, default=2000)
    ap.add_argument("--producers", type=int, default=256,
                    help="fan width for the dependency benchmarks")
    ap.add_argument("--slots", type=int, default=2,
                    help="pilot slots for the dependency benchmarks; few "
                         "slots keep the producer-settle churn away from "
                         "the measured launch window on small containers")
    ap.add_argument("--repeats", type=int, default=5,
                    help="repeat each measurement, keep the best: single "
                         "samples on a shared 2-core container swing "
                         "several-fold with scheduling noise, so min-of-N "
                         "estimates the machine floor for both sides")
    ap.add_argument("--min-speedup", type=float, default=0.0,
                    help="exit nonzero if the journaled record path is not "
                         "at least this much faster than the PR-2 "
                         "synchronous baseline (0 = report only)")
    ap.add_argument("--min-fanin-speedup", type=float, default=0.0,
                    help="exit nonzero if fan-in launch latency is not at "
                         "least this much lower than the PR-2 callback/"
                         "Timer baseline (0 = report only)")
    ap.add_argument("--scratch", default=None,
                    help="journal scratch dir (default: a temp dir, "
                         "removed afterwards)")
    ap.add_argument("--out", default=str(Path(__file__).resolve().parent.parent
                                         / "BENCH_statepath.json"))
    args = ap.parse_args(argv)

    scratch = Path(args.scratch or tempfile.mkdtemp(prefix="exp5_scratch_"))
    scratch.mkdir(parents=True, exist_ok=True)
    results = {"config": {k: getattr(args, k) for k in
                          ("tasks", "records", "lookups", "producers",
                           "slots", "repeats")}}

    def fresh(name, i):
        p = scratch / f"{name}_{i[0]}.jsonl"
        i[0] += 1
        if p.exists():
            p.unlink()
        return str(p)

    try:
        print("# record path: journaled lifecycle, per task")
        # interleave the A/B repeats: running all baseline samples before
        # all candidate samples lets slow machine drift (shared-container
        # load, frequency scaling) land entirely on one side of the
        # ratio; alternating samples both sides across the same windows,
        # so min-of-N estimates both floors under comparable conditions
        i = [0]
        sync_samples, wb_samples = [], []
        for _ in range(max(1, args.repeats)):
            sync_samples.append(bench_record(
                SyncStateStore, args.tasks, fresh("sync", i)))
            wb_samples.append(bench_record(
                StateStore, args.tasks, fresh("wb", i)))
        sync_rec, wb_rec = min(sync_samples), min(wb_samples)
        # gate statistic: the *median per-window ratio*.  Each interleaved
        # repeat is one window in which both sides ran back to back, so
        # its ratio is drift-free; the median across windows discards the
        # windows a background burst poisoned.  (The ratio of global
        # minima mixes floors from different windows and swings past the
        # gate either way on a shared 2-core container.)
        pair = sorted(s / w for s, w in zip(sync_samples, wb_samples))
        rec_speedup = pair[len(pair) // 2]
        results["record"] = {"sync_us_per_task": sync_rec * 1e6,
                             "write_behind_us_per_task": wb_rec * 1e6,
                             "speedup": rec_speedup,
                             "speedup_of_mins": sync_rec / wb_rec}
        print(f"  sync (PR-2):    {sync_rec * 1e6:9.1f} us/task")
        print(f"  write-behind:   {wb_rec * 1e6:9.1f} us/task"
              f"   ({rec_speedup:.1f}x lower, median window ratio)")

        print("# completed_result: restart lookup latency")
        sync_lk = bench_lookup(SyncStateStore, args.records, args.lookups,
                               fresh("synclk", i))
        wb_lk = bench_lookup(StateStore, args.records, args.lookups,
                             fresh("wblk", i))
        lk_speedup = sync_lk / wb_lk
        results["lookup"] = {"sync_us_per_lookup": sync_lk * 1e6,
                             "indexed_us_per_lookup": wb_lk * 1e6,
                             "speedup": lk_speedup,
                             "records": args.records}
        print(f"  linear scan (PR-2): {sync_lk * 1e6:9.1f} us/lookup "
              f"@ {args.records} records")
        print(f"  indexed:            {wb_lk * 1e6:9.1f} us/lookup"
              f"   ({lk_speedup:.0f}x lower)")

        print(f"# dependency resolution: {args.producers}-wide fan-in/out")
        base, new = [], []
        for _ in range(max(1, args.repeats)):     # interleaved (see above)
            base.append(bench_fanin(BaselineDFK, args.producers,
                                    args.slots))
            new.append(bench_fanin(DataFlowKernel, args.producers,
                                   args.slots))

        def mins(rows, k):
            return min(r[k] for r in rows)

        b_in = mins(base, "fanin_launch_s")
        n_in = mins(new, "fanin_launch_s")
        fanin_speedup = b_in / n_in
        results["fanin"] = {
            "baseline_launch_ms": b_in * 1e3,
            "batched_launch_ms": n_in * 1e3,
            "baseline_total_ms": mins(base, "fanin_total_s") * 1e3,
            "batched_total_ms": mins(new, "fanin_total_s") * 1e3,
            "speedup": fanin_speedup}
        b_out = mins(base, "fanout_launch_s")
        n_out = mins(new, "fanout_launch_s")
        results["fanout"] = {
            "baseline_launch_ms": b_out * 1e3,
            "batched_launch_ms": n_out * 1e3,
            "baseline_total_ms": mins(base, "fanout_total_s") * 1e3,
            "batched_total_ms": mins(new, "fanout_total_s") * 1e3,
            "speedup": b_out / n_out}
        print(f"  fan-in  launch latency: PR-2 {b_in * 1e3:7.2f} ms   "
              f"batched {n_in * 1e3:7.2f} ms   ({fanin_speedup:.1f}x lower)")
        print(f"  fan-out launch latency: PR-2 {b_out * 1e3:7.2f} ms   "
              f"batched {n_out * 1e3:7.2f} ms   ({b_out / n_out:.1f}x lower)")
    finally:
        if args.scratch is None:
            shutil.rmtree(scratch, ignore_errors=True)

    out = Path(args.out)
    out.write_text(json.dumps(results, indent=2))
    print(f"wrote {out}")
    failures = []
    if args.min_speedup and rec_speedup < args.min_speedup:
        failures.append(f"record path {rec_speedup:.2f}x < required "
                        f"{args.min_speedup:.1f}x")
    if args.min_fanin_speedup and fanin_speedup < args.min_fanin_speedup:
        failures.append(f"fan-in latency {fanin_speedup:.2f}x < required "
                        f"{args.min_fanin_speedup:.1f}x")
    if failures:
        raise SystemExit("REGRESSION: " + "; ".join(failures))
    return results


if __name__ == "__main__":
    main()
