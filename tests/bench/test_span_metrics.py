"""The readers of the program's spans (``bench/spans.py`` and the five
``bench/metrics`` files over it), on synthetic channels and on a traced
run of a cell on the CPU."""
from __future__ import annotations

import pathlib
import sys
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import harness, manifest, spans  # noqa: E402

OPEN = 1_000_000
SECONDS = 1e-3                       # the window: [OPEN, OPEN + 1e6) ns
CLOSE = OPEN + 1_000_000
SPAN_METRICS = ("queue_wait_us", "flush_host_us", "engine_h2d_us",
                "engine_launch_us", "engine_d2h_us")


def ctx(**channels):
    return SimpleNamespace(
        channels={name.replace("_", "."): np.asarray(rows, np.float64)
                  for name, rows in channels.items()},
        t_open=OPEN, window={"seconds": SECONDS})


def read(metric, c):
    return manifest.metric_reader(metric).read(c)


def span(start, dur, span_id, parent=0, *attrs):
    return [*attrs, start, dur, span_id, parent]


@pytest.mark.parametrize("metric,channel", [
    ("queue_wait_us", "pipeline_wait"), ("engine_h2d_us", "engine_h2d"),
    ("engine_launch_us", "engine_launch"), ("engine_d2h_us", "engine_d2h")])
def test_mean_span_readers_count_only_spans_that_start_in_the_window(
        metric, channel):
    rows = [span(OPEN - 10, 99_000, 1),          # began before the open
            span(OPEN, 2_000, 2, 9),
            span(OPEN + 500_000, 4_000, 3, 9),
            span(CLOSE - 1, 6_000, 4, 9),
            span(CLOSE, 50_000, 5, 9)]           # began at the close
    assert read(metric, ctx(**{channel: rows})) == pytest.approx(4.0)
    assert read(metric, ctx(**{channel: rows[:1]})) is None
    assert read(metric, ctx()) is None


def test_flush_self_time_counts_overlapping_children_once():
    flushes = [span(OPEN + 1000, 1000, 1, 0, 0, 4096),
               span(OPEN + 5000, 500, 2, 0, 0, 2048),
               span(CLOSE + 10, 800, 3, 0, 0, 1024)]   # outside the window
    medium = [span(OPEN + 1100, 300, 10, 1, 100, 300),
              span(OPEN + 1300, 300, 11, 1, 100, 300),  # overlaps the first
              span(OPEN + 1350, 50, 12, 1, 100, 50),    # inside the second
              span(OPEN + 5100, 100, 13, 2, 100, 100),
              span(OPEN + 5100, 100, 14, 3, 100, 100)]  # another flush's
    large = [span(OPEN + 1900, 400, 15, 1, 4000, 400)]  # runs past its end
    c = ctx(pipeline_flush=flushes, tier_medium=medium, tier_large=large)
    # flush 1: 1000 - [1100, 1600) - [1900, 2000) = 400; flush 2: 400
    assert read("flush_host_us", c) == pytest.approx(0.4)
    c = ctx(pipeline_flush=flushes)                    # no engine calls
    assert read("flush_host_us", c) == pytest.approx(0.75)
    got = spans.self_ns(np.asarray(flushes[:1], np.float64),
                        np.asarray(medium + large, np.float64))
    assert got.tolist() == [400.0]


def test_readers_stay_silent_on_a_program_without_spans():
    """A program whose channels hold the rows ``(cause, fused_batch)`` and
    ``(batch, wall_ns)`` alone, and no span channels."""
    c = ctx(pipeline_flush=[[0, 4096], [0, 4096]],
            tier_medium=[[100, 1500.0], [90, 1400.0]])
    for metric in SPAN_METRICS:
        assert read(metric, c) is None, metric


def test_every_span_metric_is_in_the_manifest_for_every_cell():
    m = manifest.manifest()
    entries = {e["name"]: e for e in m["per_layer"]}
    for metric in SPAN_METRICS:
        e = entries[metric]
        assert e["source"] == "program_span" and e["unit"] == "us"
        assert e["moves"] == "ops_per_s" and "workloads" not in e
    assert entries["queue_wait_us"]["layer"] == "pipeline"
    assert entries["engine_d2h_us"]["layer"] == entries["engine_call_us"][
        "layer"]


def test_a_traced_run_reports_every_span_metric():
    small = {"n_keys": 1 << 14, "n_keys_hint": 1 << 22, "clients": 2,
             "request_keys": 256}
    r = harness.run_cell("weblogs194d-uniform", 2 ** 35 + 3, 1.0, True,
                         require_tpu=False, overrides=small)
    assert r["correct"] is True, r["checks"]
    for metric in SPAN_METRICS:
        assert r["metrics"][metric]["value"] > 0, metric
        assert r["metrics"][metric]["unit"] == "us"
