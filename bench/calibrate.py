"""Readings that a cell's limits are set from (PERF.md, "Limits of correct").

    python3 bench/calibrate.py --workload <cell> --seeds a,b,... \\
        [--kinds program,control_fp8,...] [--seconds s]

For every seed and every kind, one run of the cell through the harness's
own ``run``: ``program`` is a sound run and gives the lower readings; any
other kind is one of the pattern's faults or the control
(``bench/generator.py``), planted in the timed path, and gives the upper
readings.  One JSON line per run: the seed, the kind, every number the
check compared and every number it reported beside them.  Needs the chips
the cell asks for, like a run; the benchmark's own runs never run this.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import generator, harness  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--kinds", default="program")
    ap.add_argument("--seconds", type=float, default=5.0)
    a = ap.parse_args()
    cell = harness.load_cell(a.workload)
    kinds = a.kinds.split(",")
    known = ("program",) + generator.faults(cell.traffic["pattern"])
    if set(kinds) - set(known):
        raise SystemExit(f"--kinds: {sorted(set(kinds) - set(known))} not "
                         f"in {known}")
    for seed in (int(x) for x in a.seeds.split(",") if x):
        for kind in kinds:
            out = {}
            res = harness.run(["--workload", a.workload, "--seed", str(seed),
                               "--seconds", str(a.seconds), "--trace", "0"],
                              fault=None if kind == "program" else kind,
                              report=out)
            print(json.dumps({
                "seed": seed, "kind": kind, "correct": res["correct"],
                **{k: c["value"] for k, c in res["checks"].items()},
                **{k: v for k, (v, _) in out["info"].items()},
                "check_s": out["check_s"]}), flush=True)


if __name__ == "__main__":
    main()
