"""``correct`` and its failures: whole runs of a cell on the CPU at a small
size, past the harness's look for a chip.  A sound run is correct; the
control (the reference over bfloat16 keys in the program's place) and each
fault planted under the timed path are not.  A key-width loss planted at
the program's boundary fails exactly where float32 merges keys, and the
program's float64 numpy tier (the witness) agrees with the reference.  No
test here depends on how the device tiers compare 64-bit keys."""
from __future__ import annotations

import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import harness  # noqa: E402
from repro.index import engine  # noqa: E402

SMALL = {"n_keys": 1 << 14, "n_keys_hint": 1 << 22, "clients": 2,
         "request_keys": 256}
SEED = 2 ** 36 + 11


def run(**kw):
    return harness.run_cell("weblogs194d-latest", SEED, 1.0, kw.pop("trace",
                                                                 False),
                            require_tpu=False, overrides=SMALL, **kw)


def checks(result):
    return {k: v["value"] for k, v in result["checks"].items()}


def test_sound_run_is_correct_and_reports_its_metrics():
    r = run(trace=True)
    assert r["correct"] is True, r["checks"]
    assert checks(r) == {"wrong_answers": 0, "unanswered_requests": 0,
                         "window_without_answers": 0}
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    got = set(r["metrics"])
    # the CPU trace has no device plane: the device readers stay silent
    assert {"flush_keys_mean", "engine_calls_per_kop", "engine_call_us",
            "window_compiles", "setup_compile_s"} <= got
    assert not got & {"search_roofline", "device_idle_pct"}
    assert r["device"]["window_s"] > 0


def test_control_in_bfloat16_is_not_correct():
    r = run(control="narrower")        # the stand-in's keys: bfloat16
    assert r["correct"] is False
    assert checks(r)["wrong_answers"] > 0


def test_control_type_is_the_first_narrower_type_that_rounds_a_key():
    seconds = np.array([0.0, 1.0, 2.0 ** 24 - 1])
    assert harness.narrower_dtype("float64", seconds) == "bfloat16"
    assert harness.narrower_dtype("float64", seconds + 2 ** 24) == "float32"
    assert harness.narrower_dtype("float32", seconds) == "bfloat16"
    with pytest.raises(ValueError):
        harness.narrower_dtype("bfloat16", seconds)
    with pytest.raises(ValueError):
        harness.narrower_dtype("float64", np.array([0.0, 1.0]))
    with pytest.raises(ValueError):
        harness.LowPrecisionReference(seconds, "float64")


def _cell(config, traffic):
    return {"name": f"{config}.{traffic}", "config": config,
            "traffic": traffic, "chips": 1}


def _float32(a):
    return np.asarray(a, np.float32).astype(np.float64)


class _Float32Program(harness.Program):
    """The program as the harness builds it, but on the key column rounded
    to float32 and called with every query rounded to float32."""

    def __init__(self, keys, cfg, monitor, n_keys_hint):
        super().__init__(_float32(keys), cfg, monitor, n_keys_hint)
        lookup = self.call
        self.call = lambda q: lookup(_float32(q))

    def warm(self, keys, sampler, seed, mix):
        return super().warm(_float32(keys), sampler, seed, mix)


@pytest.mark.parametrize("config, keys_collide", [("weblogs-2e26", True),
                                                  ("weblogs-194d", False)])
def test_keys_narrowed_to_float32_fail_only_where_they_collide(
        monkeypatch, config, keys_collide):
    monkeypatch.setattr(harness, "Program", _Float32Program)
    r = harness.run_cell(_cell(config, "probe-uniform"), SEED, 1.0, False,
                         require_tpu=False,
                         overrides=dict(SMALL, n_keys=1 << 20))
    c = checks(r)
    assert r["correct"] is not keys_collide
    assert (c["wrong_answers"] > 0) is keys_collide
    assert c["unanswered_requests"] == 0


@pytest.mark.parametrize("config, traffic", [("weblogs-2e26", "probe-uniform"),
                                             ("maps-2e26", "probe-zipf")])
def test_the_witness_sides_with_the_reference(config, traffic):
    r = harness.run_cell(_cell(config, traffic), SEED, 1.0, False,
                         require_tpu=False, witness=True,
                         overrides=dict(SMALL, n_keys=1 << 20))
    assert checks(r)["witness_wrong_answers"] == 0


def _patch_lookup(monkeypatch, spoil):
    real = engine.DispatchEngine.lookup

    def lookup(self, queries):
        out = np.array(real(self, queries), copy=True)
        spoil(out)
        return out
    monkeypatch.setattr(engine.DispatchEngine, "lookup", lookup)


def test_an_answer_altered_where_it_is_produced_fails(monkeypatch):
    def bump_first(out):
        out[:1] += 1
    _patch_lookup(monkeypatch, bump_first)
    r = run()
    assert r["correct"] is False and checks(r)["wrong_answers"] > 0


def test_half_of_each_batch_left_out_fails(monkeypatch):
    def drop_half(out):
        out[out.size // 2:] = -1
    _patch_lookup(monkeypatch, drop_half)
    r = run()
    assert r["correct"] is False and checks(r)["wrong_answers"] > 0


@pytest.mark.parametrize("generator", ["weblogs", "maps"])
def test_key_columns_are_sorted_and_fixed_by_the_data_seed(generator):
    cfg = {"generator": generator, "data_seed": 5, "key_dtype": "float32"}
    a = harness.make_keys(cfg, 1 << 15)
    assert np.all(np.diff(a) >= 0)
    assert np.array_equal(a, a.astype(np.float32).astype(np.float64))
    assert np.array_equal(a, harness.make_keys(cfg, 1 << 15))
    with pytest.raises(ValueError):
        harness.make_keys(dict(cfg, key_dtype="float16"), 16)


@pytest.mark.parametrize("config", ["weblogs-194d", "weblogs-2e26"])
def test_weblogs_keys_are_whole_seconds_inside_the_span(config):
    cfg = harness.manifest.config_file(config)
    a = harness.make_keys(cfg, 1 << 16)
    span = cfg["generator_params"]["span_s"]
    assert np.all(np.diff(a) >= 0) and a[0] >= 0 and a[-1] < span
    assert np.array_equal(a, np.floor(a))
    # the stand-in's span keeps every second a float32; the full year not
    exact = np.array_equal(a, a.astype(np.float32))
    assert exact == (span <= 2 ** 24)


def test_a_traffic_verb_the_harness_does_not_drive_is_refused(monkeypatch):
    real = harness.manifest.traffic

    def traffic(name):
        return dict(real(name), verb="range")
    monkeypatch.setattr(harness.manifest, "traffic", traffic)
    with pytest.raises(ValueError, match="verb"):
        run()
