"""Run one benchmark cell once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints the numbers its check compares (each beside its limit) as the last
lines of stderr and one JSON object as the last line of stdout.  Exits 2,
printing no result, when JAX finds no TPU or fewer chips than the cell asks
for.  See ``bench/harness.py``.
"""
import time

T_START = time.monotonic()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
