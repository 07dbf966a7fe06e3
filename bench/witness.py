#!/usr/bin/env python3
"""A configuration run like a cell, whether or not ``BENCHMARK.json`` has a
cell on it, with a witness beside the reference.

    python3 bench/witness.py --config <config> --traffic <mix> \\
        --seed <n> --seconds <s>

The program serves the mix through ``open_pipeline`` as in a run; once the
window has closed, the program's own float64 numpy tier answers the same
queries.  The last lines of standard error give ``wrong_answers`` (the
served path against ``np.searchsorted`` on the configuration's key column)
and ``witness_wrong_answers`` (the numpy tier against the same).  Not part
of the benchmark's runs.

Where the program stands on the 64-bit configurations that no cell runs
yet: its device tiers compare keys as float32, so keys that round to one
float32 get one answer.  On one TPU v5e, ``maps-2e26`` x ``probe-zipf``
got 991,186 of 2,162,688 answers wrong (45.8%) and ``weblogs-2e26`` x
``probe-uniform`` 21.4%; the numpy tier got none wrong on either.
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
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cfg = harness.manifest.config_file(args.config)
    cell = {"name": f"{args.config}.{args.traffic}", "config": args.config,
            "traffic": args.traffic, "chips": cfg["chips"]}
    try:
        result = harness.run_cell(cell, args.seed, args.seconds, False,
                                  t_start_ns=T_START_NS, witness=True)
    except harness.NoChip as exc:
        print(f"witness: {exc}", file=sys.stderr)
        return 2
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
