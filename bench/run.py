"""Run one cell of the benchmark on the chip this machine holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints an ``info`` line (device, geometry, sizes), then as its last line the
result object; the numbers compared with the reference, each with its
limit, end standard error.  Exits 2, printing no result, where JAX finds no
TPU or fewer chips than the cell asks for.
"""

import time

T_START = time.perf_counter()

import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
