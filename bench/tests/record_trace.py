"""Trim a traced run's profile to a test fixture: one whole program call.

    python3 bench/tests/record_trace.py --workload <cell> --program <prefix> \
        --out bench/tests/data/<name>.json.gz [--skip <n>]

Run after ``bench/run.py --workload <cell> ... --trace 1``, which leaves its
profile under ``.bench/trace/<cell>/``.  Keeps the first chip's operations
and programs that lie inside one call of the program whose name starts with
``prefix`` (the ``skip``-th after the first that lies wholly inside the
run's window), with that call as the fixture's window, in the form
``bench/trace.py``'s ``from_json`` reads.  Prints what it kept.
"""
import argparse
import gzip
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import harness, trace as tr  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(prog="bench/tests/record_trace.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--program", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--skip", type=int, default=1)
    args = ap.parse_args(argv)
    full = tr.load(tr.find_xplane(harness.OUT / "trace" / args.workload),
                   devices=1)
    dev = full.devices[0]
    calls = sorted((t, t + d) for n, t, d in dev["modules"]
                   if n.startswith(args.program)
                   and full.t0 <= t and t + d <= full.t1)
    t0, t1 = calls[args.skip]
    inside = lambda evs: [list(e) for e in evs                # noqa: E731
                          if t0 <= e[1] and e[1] + e[2] <= t1]
    obj = {"devices": [{"ops": inside(dev["ops"]),
                        "modules": inside(dev["modules"])}],
           "host": [["window", t0, t1 - t0]],
           "recorded": {"workload": args.workload,
                        "program": args.program, "call_s": (t1 - t0) * 1e-9,
                        "calls_in_window": len(calls)}}
    with gzip.open(args.out, "wt") as fh:
        json.dump(obj, fh)
    print(json.dumps({"out": args.out, "ops": len(obj["devices"][0]["ops"]),
                      **obj["recorded"]}))


if __name__ == "__main__":
    main()
