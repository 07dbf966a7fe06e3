#!/usr/bin/env python3
"""The benchmark's command: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs only on a TPU: elsewhere it exits non-zero and prints no result.
"""
import time

T_START_NS = time.perf_counter_ns()   # set-up is timed from here

import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)                # import bench as a package
sys.path.insert(1, str(ROOT / "src"))  # the system under test

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start_ns=T_START_NS))
