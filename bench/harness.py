"""The benchmark's harness: finds a cell's files by name and runs it once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` names the cell's configuration and traffic mix.  Each is
a data file found by that name (``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json``); the cell's own parameters and the limits
of its check are in ``bench/workloads/<cell>.json``; a per-layer metric is
read by ``bench/metrics/<metric>.py``.  The traffic file names the pattern
that drives it, ``bench/patterns/<pattern>.py`` (see ``bench/generator.py``).
Nothing here needs an edit when a cell, a configuration, a traffic mix, a
pattern or a metric is added.

A run: look for the chips the cell asks for (none found: exit 2, no result);
set up (weights from the seed, the cell's shapes warmed, the first steps or
chains that the check needs); measure for ``--seconds``, traced with
``--trace 1``; read the device memory peak; free the program's state; run
the check against the float32 reference; print the numbers compared beside
their limits as the last lines of stderr, and one JSON line as the last
line of stdout.
"""
from __future__ import annotations

import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
OUT = ROOT / ".bench"                  # traces of --trace 1 runs (ignored)


class NoChip(SystemExit):
    pass


def load_json(path: Path):
    with open(path) as fh:
        return json.load(fh)


def load_cell(name: str) -> SimpleNamespace:
    """Everything that belongs to one cell, from the files its names lead to."""
    bench = load_json(ROOT / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{[w['name'] for w in bench['workloads']]}")
    conf_entry = next(c for c in bench["configs"]
                      if c["name"] == entry["config"])
    workload = load_json(BENCH / "workloads" / f"{name}.json")
    for key in ("config", "traffic", "chips"):
        if workload[key] != entry[key]:
            raise SystemExit(f"bench/workloads/{name}.json: {key} "
                             f"{workload[key]!r} != BENCHMARK.json's "
                             f"{entry[key]!r}")
    applies = lambda m: name in m.get("workloads", [name])  # noqa: E731
    return SimpleNamespace(
        name=name, chips=entry["chips"],
        config=load_json(ROOT / conf_entry["file"]),
        traffic=load_json(BENCH / "traffic" / f"{entry['traffic']}.json"),
        workload=workload,
        run_seconds=bench["run_seconds"],
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)])


def program_config(conf: dict):
    """The program's ModelConfig for a configuration file: the registry's
    entry with the file's ``program`` overrides, checked against every size
    the file states under ``sizes`` (file key -> ModelConfig field)."""
    import dataclasses

    from repro.configs import get_config
    cfg = dataclasses.replace(get_config(conf["registry"]),
                              **conf.get("program", {}))
    for key, field in conf["fields"].items():
        if getattr(cfg, field) != conf["sizes"][key]:
            raise SystemExit(f"{conf['name']}: {key} = {conf['sizes'][key]} "
                             f"in the file, {getattr(cfg, field)} in the "
                             f"program's config")
    return cfg


def require_chips(n: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n:
        raise NoChip(f"needs {n} TPU chip(s); JAX found {len(devices)} "
                     f"{devices[0].platform} device(s)")
    return devices[:n]


def enable_compile_cache() -> str:
    """JAX_COMPILATION_CACHE_DIR where it is set, else the program's fixed
    ``<checkout>/.jax_cache``; every program is cached, however fast."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache as enable
    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


class CompileCounter:
    """Compilations and persistent-cache loads, stamped on the host clock."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.event = dispatch.BACKEND_COMPILE_EVENT
        self.stamps = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.event:
            self.stamps.append(time.monotonic())

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t in self.stamps)


def memory_peak(device) -> int:
    """Peak bytes on one chip: its buffers' peak (``peak_bytes_in_use``)
    and the region the TPU runtime reserves for compiled programs' scratch
    (``peak_bytes_reserved``), which the first leaves out."""
    s = device.memory_stats() or {}
    return s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)


class GcPauses:
    """Python's garbage collections, with their lengths on the host clock."""

    def __init__(self):
        import gc
        self.t, self.pauses = None, []
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self.t = time.monotonic()
        elif self.t is not None:
            self.pauses.append((self.t, time.monotonic() - self.t,
                                info["generation"]))

    def between(self, t0: float, t1: float) -> list:
        return [p for p in self.pauses if t0 <= p[0] <= t1]


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def load_metric(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def quantile(values, q: float):
    """The q-quantile of ``values`` by linear interpolation (q in [0, 1])."""
    xs = sorted(values)
    if not xs:
        return None
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def run(argv=None, t_start=None, allow_cpu=False, fault=None,
        cell=None, report=None) -> dict:
    """One run of one cell; returns the result line's object.

    ``allow_cpu`` skips the look for a chip, ``fault`` plants one of the
    pattern's faults in the timed path or puts the control in the
    program's place (``bench/generator.py``), and ``cell`` replaces the
    cell that ``--workload`` names: all three are for the harness's tests
    and ``bench/calibrate.py`` only.  ``report``, a dict where given,
    receives the numbers reported beside the result (``info``, the check's
    seconds ``check_s``).
    """
    import argparse
    t_start = time.monotonic() if t_start is None else t_start
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("--seed must be a non-negative integer")

    cell = cell or load_cell(args.workload)
    t_run = time.monotonic()
    import jax
    devices = (jax.devices()[:cell.chips] if allow_cpu
               else require_chips(cell.chips))
    t_chips = time.monotonic()
    enable_compile_cache()
    compiles = CompileCounter()
    gcs = GcPauses()
    from bench import generator, trace as tr
    if fault is not None and fault not in generator.faults(
            cell.traffic["pattern"]):
        raise ValueError(f"fault {fault!r} is not one of "
                         f"{generator.faults(cell.traffic['pattern'])}")
    pattern = generator.load_pattern(cell.traffic["pattern"]).Pattern(
        cell, args.seed, devices, fault=fault)
    pattern.setup()
    setup_s = time.monotonic() - t_start
    phases = dict(start=t_run - t_start, chips=t_chips - t_run,
                  **pattern.phases.s)
    print("info setup phases (s): " + " ".join(
        f"{k}={v:.3f}" for k, v in phases.items()), file=sys.stderr)

    trace_dir = OUT / "trace" / cell.name
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    win = pattern.window(args.seconds)
    trace = None
    if args.trace:
        jax.profiler.stop_trace()
        trace = tr.load(tr.find_xplane(trace_dir), devices=len(devices))
    peak = max(memory_peak(d) for d in devices)
    pattern.close()

    t_check = time.monotonic()
    checks = pattern.check()
    check_s = time.monotonic() - t_check
    print(f"info setup_s={setup_s:.3f} window_s={win['t1'] - win['t0']:.3f} "
          f"check_s={check_s:.3f}", file=sys.stderr)
    pauses = gcs.between(win["t0"], win["t1"])
    info = dict(getattr(pattern, "info", {}), gc_in_window=(
        len(pauses), max(((round(d * 1e3, 3), g) for _, d, g in pauses),
                         default=None)))
    for name, (value, detail) in info.items():
        print(f"info {name} (not compared): {value!r} {detail!r}",
              file=sys.stderr)
    if report is not None:
        report.update(info=info, check_s=check_s)
    correct = all(c["value"] <= c["limit"] for c in checks) and \
        win["failed"] == 0
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": win["attempted"],
              "failed": win["failed"]}
    if not args.trace:
        values = dict(win["end_to_end"], setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    else:
        ctx = SimpleNamespace(
            cell=cell, trace=trace, window=win, chips=len(devices),
            peaks=tr.peaks_for(devices[0].device_kind),
            window_compiles=compiles.between(win["t0"], win["t1"]))
        metrics = {}
        for m in cell.per_layer:
            value = load_metric(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = trace.busy_s()
        device["window_s"] = trace.window_s
        result["breakdown"] = trace.breakdown()
    result.update(metrics=metrics, device=device,
                  checks={c["name"]: {"value": _number(c["value"]),
                                      "limit": c["limit"]} for c in checks})
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    return result


def _number(x):
    """A check's value for the JSON line: a finite number, or its name
    ("nan", "inf") where it is not one."""
    import math
    return x if math.isfinite(x) else repr(float(x))


def main(argv=None, t_start=None) -> int:
    try:
        result = run(argv, t_start)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0
