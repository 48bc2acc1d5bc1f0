#!/usr/bin/env python3
"""Run one benchmark cell and print its result as the last line of stdout.

    python bench/run.py --workload higgs.train --seed 7 --seconds 40 --trace 0

It refuses to run (exit code 3, no result) when JAX finds no TPU or fewer
chips than the cell asks for.
"""

import time

T_PROCESS = time.perf_counter()  # set-up is timed from here

import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
# the script's own directory must not shadow installed modules; the
# system under test is the repository's package under src/
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], t_process=T_PROCESS))
