"""Benchmark plumbing: timing + CSV rows + JSON result files."""
from __future__ import annotations

import csv
import json
import pathlib
import time
from typing import Iterable

from repro.compile_cache import enable_compile_cache

OUT_DIR = pathlib.Path(__file__).parent / "out"

enable_compile_cache()


def _jsonable(obj):
    """numpy scalars/arrays -> plain Python (json.dumps default hook)."""
    if hasattr(obj, "item") and getattr(obj, "ndim", 0) == 0:
        return obj.item()
    if hasattr(obj, "tolist"):
        return obj.tolist()
    raise TypeError(f"not JSON serializable: {type(obj).__name__}")


def write_json(name: str, obj, path: pathlib.Path | str | None = None
               ) -> pathlib.Path:
    """Write a benchmark result object as JSON (default: out/<name>.json)."""
    if path is None:
        OUT_DIR.mkdir(exist_ok=True)
        path = OUT_DIR / f"{name}.json"
    path = pathlib.Path(path)
    path.write_text(json.dumps(obj, indent=2, sort_keys=True,
                               default=_jsonable) + "\n")
    return path


def timeit(fn, *args, repeats: int = 3, warmup: int = 1) -> float:
    """Median wall seconds of fn(*args)."""
    for _ in range(warmup):
        fn(*args)
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn(*args)
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def write_csv(name: str, header: list[str], rows: Iterable[tuple]):
    OUT_DIR.mkdir(exist_ok=True)
    path = OUT_DIR / f"{name}.csv"
    with path.open("w", newline="") as f:
        w = csv.writer(f)
        w.writerow(header)
        for r in rows:
            w.writerow(r)
    return path


def emit(bench: str, metric: str, value: float, derived: str = ""):
    """The run.py contract: ``name,us_per_call,derived`` CSV lines."""
    print(f"{bench}.{metric},{value:.4g},{derived}")
