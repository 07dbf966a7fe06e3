"""Spans on the served path (``repro.index.telemetry.Monitor.span``).

Rows end in ``(start_ns, dur_ns, span_id, parent_id)``; the engine's
``engine.*`` steps nest under the dispatch ``tier.*`` call, which nests
under the pipeline's ``pipeline.flush``, and every request's
``pipeline.wait`` names the flush that served it.  The ``tier.*`` rows
still lead with ``(batch_size, wall_ns)``.  With no monitor nothing is
built, and under the profiler the spans are host events of the same name.
"""
from __future__ import annotations

import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from repro.core.cost_model import fit_tier_curves  # noqa: E402
from repro.index import Monitor  # noqa: E402
from repro.index.telemetry import (CH_D2H, CH_FLUSH, CH_H2D,  # noqa: E402
                                   CH_IDLE, CH_LAUNCH, CH_MERGE, CH_ROUTE,
                                   CH_WAIT, SPAN_COLUMNS)
from repro.serve import FitSpec, open_index, open_pipeline  # noqa: E402

N_KEYS = 1 << 12
# a plan for a larger table: several shards, device tiers for ~100-key calls
SPEC = FitSpec(error=64, hardware="tpu", n_keys_hint=1 << 22)
TIERS = ("tier.small", "tier.medium", "tier.large")


def _keys():
    rng = np.random.default_rng(7)
    return np.unique(rng.integers(0, 1 << 24, N_KEYS)).astype(np.float64)


def _serve(monitor, requests=6, size=256):
    """Concurrent requests through ``open_pipeline``; returns the answers,
    the queries and the pipeline's shard count."""
    keys = _keys()
    kw = {} if monitor is None else {"monitor": monitor}
    pipe = open_pipeline(keys, SPEC, assume_sorted=True, prewarm=False, **kw)
    rng = np.random.default_rng(3)
    qs = [keys[rng.integers(0, keys.size, size)] for _ in range(requests)]
    try:
        futs = [pipe.lookup_async(q) for q in qs]
        got = [f.result(timeout=120) for f in futs]
        shards = pipe.service.n_shards
    finally:
        pipe.close()
    for q, g in zip(qs, got):
        np.testing.assert_array_equal(g, np.searchsorted(keys, q, "left"))
    return got, qs, shards


@pytest.fixture(scope="module")
def served():
    mon = Monitor(capacity=1 << 16)
    _, qs, shards = _serve(mon)
    return mon, qs, shards


def _by_id(rows):
    return {int(r[-2]): r for r in rows}


def _inside(child, parent):
    return (parent[-4] <= child[-4]
            and child[-4] + child[-3] <= parent[-4] + parent[-3])


def test_span_rows_end_in_start_duration_id_and_parent(served):
    mon, qs, shards = served
    assert shards > 1 and len(SPAN_COLUMNS) == 4
    widths = {CH_FLUSH: 6, CH_WAIT: 4, CH_ROUTE: 4, CH_MERGE: 4, CH_H2D: 4,
              CH_LAUNCH: 4, CH_D2H: 4}
    for name, width in widths.items():
        rows = mon.channel(name)
        assert rows.ndim == 2 and rows.shape[0] > 0, name
        assert rows.shape[1] == width, name
        assert (rows[:, -3] >= 0).all() and (rows[:, -2] > 0).all(), name
    ids = np.concatenate([mon.channel(n)[:, -2] for n in mon.channels()
                          if n in widths or n in TIERS])
    assert np.unique(ids).size == ids.size           # one id per span
    flushes = mon.channel(CH_FLUSH)
    assert flushes[:, 1].sum() == sum(q.size for q in qs)  # (cause, keys)


def test_engine_steps_nest_under_the_tier_call_under_the_flush(served):
    mon, _, _ = served
    flushes = _by_id(mon.channel(CH_FLUSH))
    tiers = {}
    for name in TIERS:
        rows = mon.channel(name)
        if rows.size:
            tiers.update(_by_id(rows))
    device = {i: r for n in ("tier.medium", "tier.large")
              for i, r in (_by_id(mon.channel(n)) if mon.channel(n).size
                           else {}).items()}
    assert device, "the plan routes ~100-key shard calls to a device tier"
    for tier in tiers.values():
        assert int(tier[-1]) in flushes
        assert _inside(tier, flushes[int(tier[-1])])
    steps = [mon.channel(n) for n in (CH_H2D, CH_LAUNCH, CH_D2H)]
    assert len({s.shape[0] for s in steps}) == 1
    assert steps[0].shape[0] == len(device)          # three per device call
    for rows in steps:
        for r in rows:
            assert int(r[-1]) in device
            assert _inside(r, device[int(r[-1])])
    for name in (CH_ROUTE, CH_MERGE):
        for r in mon.channel(name):
            assert int(r[-1]) in flushes
            assert _inside(r, flushes[int(r[-1])])


def test_each_request_wait_names_the_flush_that_served_it(served):
    mon, qs, _ = served
    flushes = _by_id(mon.channel(CH_FLUSH))
    waits = mon.channel(CH_WAIT)
    assert waits.shape[0] == len(qs)
    for w in waits:
        flush = flushes[int(w[-1])]
        assert w[-4] + w[-3] == flush[-4]     # the wait ends as it begins
    # the flush's fused size is the sum of the requests it waited for
    for fid, flush in flushes.items():
        n = int((waits[:, -1] == fid).sum())
        assert flush[1] == n * qs[0].size


def test_tier_rows_keep_batch_and_wall_first_and_the_same_fit(served):
    mon, _, _ = served
    samples = mon.tier_samples()
    assert samples
    for tier, pairs in samples.items():
        rows = mon.channel("tier." + tier)
        assert rows.shape[1] == 2 + 4
        np.testing.assert_array_equal(pairs, rows[:, :2])
        np.testing.assert_array_equal(rows[:, 1], rows[:, -3])  # wall = dur
        assert (rows[:, 0] >= 1).all()
    assert fit_tier_curves(samples, min_samples=2) == fit_tier_curves(
        {t: mon.channel("tier." + t)[:, :2] for t in samples},
        min_samples=2)


def test_spans_nest_by_thread_and_record_their_attributes():
    mon = Monitor()
    with mon.span("outer", 7) as outer:
        with mon.span("inner", wall=True) as inner:
            pass
        sid = mon.record_span("after", outer.start_ns, 5, outer.span_id)
    (o,), (i,), (a,) = (mon.channel(n) for n in ("outer", "inner", "after"))
    assert o[0] == 7 and o[-2] == outer.span_id and o[-1] == 0
    assert i[-2] == inner.span_id and i[-1] == outer.span_id
    assert i[0] == i[-3]                              # wall, then the span
    assert a[-2] == sid and a[-1] == outer.span_id and a[-3] == 5
    with mon.span("next"):
        pass
    assert mon.channel("next")[0, -1] == 0            # the stack unwound


def test_no_monitor_builds_no_span(monkeypatch):
    import jax.profiler

    def refuse(name, **kw):
        raise AssertionError(f"a span was built: {name}")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    _serve(None, requests=3)                         # answers, builds none
    keys = _keys()
    svc = open_index(keys, SPEC, assume_sorted=True, monitor=Monitor())
    with pytest.raises(AssertionError, match="a span was built"):
        svc.lookup(keys[:256])                       # the patch does bite


def test_spans_are_host_events_on_the_profiler_trace(tmp_path):
    import jax

    from bench import xplane

    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(Monitor(), requests=3)
    finally:
        jax.profiler.stop_trace()
    host = xplane.planes(xplane.find_trace(str(tmp_path)))[xplane.HOST_PLANE]
    names = {name for events in host.values() for name, _, _ in events}
    assert {CH_FLUSH, CH_IDLE, CH_ROUTE, CH_D2H} <= names
