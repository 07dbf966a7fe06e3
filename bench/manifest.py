"""Finds a cell's files by the names ``BENCHMARK.json`` gives them."""
from __future__ import annotations

import functools
import importlib.util
import json
import pathlib
from types import ModuleType

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
MANIFEST = ROOT / "BENCHMARK.json"


@functools.lru_cache(maxsize=1)
def manifest() -> dict:
    return json.loads(MANIFEST.read_text())


def _by_name(entries: list[dict], name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise KeyError(f"no {what} named {name!r} in {MANIFEST.name}")


def workload(name: str) -> dict:
    return _by_name(manifest()["workloads"], name, "workload")


def config(name: str) -> dict:
    """The configuration's file (``configs/<name>.json``), as run."""
    entry = _by_name(manifest()["configs"], name, "config")
    return json.loads((ROOT / entry["file"]).read_text())


def config_file(name: str) -> dict:
    """``configs/<name>.json``, whether or not a cell of the manifest
    uses it."""
    return json.loads((BENCH_DIR / "configs" / f"{name}.json").read_text())


def traffic(name: str) -> dict:
    return json.loads((BENCH_DIR / "traffic" / f"{name}.json").read_text())


def peaks(device_kind: str) -> dict:
    """The chip's peaks; a kind that is not in the table is an error."""
    table = json.loads((BENCH_DIR / "peaks.json").read_text())
    try:
        return table["kinds"][device_kind]
    except KeyError:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json; "
                       "add its published peaks with their source") from None


def metrics_of(cell: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports."""
    return [m for m in manifest()[kind]
            if cell in m.get("workloads", [cell])]


@functools.lru_cache(maxsize=None)
def load_module(subdir: str, name: str) -> ModuleType:
    """``bench/<subdir>/<name>.py`` as a module (names may hold ``-``)."""
    path = BENCH_DIR / subdir / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench.{subdir}.{name.replace('-', '_')}", path)
    if spec is None or not path.exists():
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def dataset(generator: str) -> ModuleType:
    return load_module("datasets", generator)


def distribution(name: str) -> ModuleType:
    return load_module("traffic", name)


def metric_reader(name: str) -> ModuleType:
    return load_module("metrics", name)
