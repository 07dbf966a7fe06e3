#!/usr/bin/env python3
"""The control of a cell's ``correct``: the reference put in the program's
place with the key column rounded to the next narrower type that rounds
some key (``harness.narrower_dtype``), driven by the cell's own traffic
and checked like a run.  It must come out not correct.

    python3 bench/control.py --workload <cell> --seed <n> --seconds <s> \\
        [--dtype float32|bfloat16]

``--dtype`` names the type instead.  Not part of the benchmark's runs.
"""
import time

T_START_NS = time.perf_counter_ns()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--dtype", default="narrower")
    args = ap.parse_args(argv)
    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  False, t_start_ns=T_START_NS,
                                  control=args.dtype)
    except harness.NoChip as exc:
        print(f"control: {exc}", file=sys.stderr)
        return 2
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
