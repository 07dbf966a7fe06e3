"""The cell benchmark's own parts, on the CPU: the trace reduction, the
window's end-to-end numbers, the roofline's byte count, the key samplers,
the manifest's files, and the command's refusal of a non-TPU backend."""
from __future__ import annotations

import json
import os
import pathlib
import re
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import driver, manifest, roofline, xplane  # noqa: E402
from bench.zipfian import Zipfian, zeta  # noqa: E402

DATA = pathlib.Path(__file__).resolve().parent / "data"
TRACE = DATA / "chip_trace_v5e.json"
SCOPED_TRACE = DATA / "chip_trace_v5e_scoped.json"
FIT_SCOPES = {"fit.prelude", "fit.kernel", "fit.bisect", "fit.snap"}
MS = 1_000_000


# ------------------------------------------------------------ trace reduction
def _tree(device_ops, host=()):
    return {"/device:TPU:0": {"XLA Ops#0": list(device_ops)},
            "/host:CPU": {"python3#0": [(xplane.WINDOW_EVENT, 0, 100)],
                          "python3#1": list(host)}}


def test_busy_is_the_union_of_overlapping_ops_inside_the_window():
    tree = _tree([("a", -10, 20), ("b", 10, 30), ("c", 50, 60),
                  ("d", 95, 130)])
    r = xplane.reduce(tree)
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx(45e-9)      # [0,30) + [50,60) + [95,100)
    assert r.idle_share == pytest.approx(0.55)
    assert r.ops == pytest.approx({"a": 20e-9, "b": 20e-9, "c": 10e-9,
                                   "d": 5e-9})
    assert sorted(s for _, s in r.gaps) == pytest.approx([20e-9, 35e-9])


def test_gaps_take_the_innermost_host_event_as_their_label():
    host = [("bench.request", 0, 100), ("PjitFunction(f)", 35, 45),
            ("TransferToDevice", 38, 42)]
    r = xplane.reduce(_tree([("k", 0, 30), ("k", 50, 100)], host))
    assert r.gaps == [("TransferToDevice", pytest.approx(20e-9))]
    r = xplane.reduce(_tree([("k", 0, 30), ("k", 50, 100)]))
    assert r.gaps == [(xplane.NO_HOST_EVENT, pytest.approx(20e-9))]
    assert r.idle_by_label() == [[xplane.NO_HOST_EVENT,
                                  pytest.approx(20e-9)]]


def test_a_trace_without_device_ops_has_no_device():
    r = xplane.reduce(_tree([]))
    assert r.devices == 0 and r.busy_s == 0 and r.gaps == []


def test_recorded_chip_trace_reduces_consistently():
    """20 ms of a traced ``weblogs-latest`` run recorded on one v5e: the
    planes as ``xplane.planes`` reads them, names cut to 100 characters."""
    raw = json.loads(TRACE.read_text())
    tree = {p: {line: [tuple(e) for e in evs] for line, evs in lines.items()}
            for p, lines in raw.items()}
    r = xplane.reduce(tree)
    assert r.devices == 1
    assert r.window_s == pytest.approx(0.02)
    assert 0 < r.busy_s < r.window_s
    idle = sum(s for _, s in r.gaps)
    assert r.busy_s + idle == pytest.approx(r.window_s, rel=1e-9)
    assert sum(r.ops.values()) >= r.busy_s * (1 - 1e-9)
    assert len(r.gaps) > 1 and all(label for label, _ in r.gaps)
    assert len(r.top_ops()) <= 10 and len(r.idle_by_label()) <= 10


def _load(path):
    raw = json.loads(path.read_text())
    return {p: {line: [tuple(e) for e in evs] for line, evs in lines.items()}
            for p, lines in raw.items()}


def _unscoped(tree):
    return {p: {line: [e[:3] for e in evs] for line, evs in lines.items()}
            for p, lines in tree.items()}


def test_scopes_split_the_op_time_and_leave_busy_and_gaps_alone():
    tree = _tree([("k", 0, 30, "fit.kernel"), ("s", 20, 40, "fit.snap"),
                  ("w", 10, 60, xplane.NO_SCOPE), ("k", 50, 70, "fit.kernel"),
                  ("p", 90, 120, "fit.prelude")])
    r = xplane.reduce(tree)
    plain = xplane.reduce(_unscoped(tree))
    assert (r.busy_s, r.gaps, r.ops) == (plain.busy_s, plain.gaps, plain.ops)
    assert r.scopes == pytest.approx({"fit.kernel": 50e-9, "fit.snap": 20e-9,
                                      xplane.NO_SCOPE: 50e-9,
                                      "fit.prelude": 10e-9})
    assert sum(r.scopes.values()) == pytest.approx(sum(r.ops.values()))
    assert plain.scopes == pytest.approx({xplane.NO_SCOPE: 130e-9})
    assert r.top_ops(2) == [["fit.kernel|k", pytest.approx(50e-9)],
                            [f"{xplane.NO_SCOPE}|w", pytest.approx(50e-9)]]
    assert r.top_scopes(1) == [["fit.kernel", pytest.approx(50e-9)]]


def test_the_innermost_fit_scope_names_an_op():
    path = ("jit(_run_on_index)/fit.prelude/jit(searchsorted)/"
            "jit(_run_on_index)/fit.bisect/cond/branch_1_fun/fit.prelude/"
            "jit(searchsorted)/vmap()/while/body/closed_call/gather:")
    assert xplane.innermost_scope(path) == "fit.prelude"
    assert xplane.innermost_scope(
        "jit(_run_on_index)/fit.kernel/cond/branch_0_fun/pallas_call:"
    ) == "fit.kernel"
    assert xplane.innermost_scope("jit(f)/while/body/gather:") == \
        xplane.NO_SCOPE
    assert xplane.innermost_scope("") == xplane.NO_SCOPE


def test_planes_read_each_device_op_scope_from_its_metadata(tmp_path):
    """An XSpace written with the protobuf classes: the scope comes from
    the ``tf_op`` stat of each op's event metadata."""
    pb = xplane._xplane_pb2()
    space = pb.XSpace()
    dev = space.planes.add(id=1, name="/device:TPU:0")
    dev.stat_metadata[7].name = xplane.SCOPE_STAT
    dev.stat_metadata[7].id = 7
    paths = {1: "jit(f)/fit.kernel/pallas_call:", 2: None,
             3: "jit(f)/fit.prelude/jit(searchsorted)/fit.bisect/gather:"}
    for k, path in paths.items():
        meta = dev.event_metadata[k]
        meta.id, meta.name = k, f"%op.{k} = f32[8] op()"
        if path is not None:
            meta.stats.add(metadata_id=7, str_value=path)
    line = dev.lines.add(id=1, name=xplane.OPS_LINE, timestamp_ns=1000)
    for k, offset_ps in ((1, 0), (2, 5000), (3, 9000), (1, 20000)):
        line.events.add(metadata_id=k, offset_ps=offset_ps,
                        duration_ps=2000)
    host = space.planes.add(id=2, name=xplane.HOST_PLANE)
    host.event_metadata[1].name = xplane.WINDOW_EVENT
    host.lines.add(id=1, name="python3", timestamp_ns=1000).events.add(
        metadata_id=1, offset_ps=0, duration_ps=30000)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    tree = xplane.planes(str(path))
    ops = tree["/device:TPU:0"][f"{xplane.OPS_LINE}#0"]
    assert [(n.split()[0], s, e, sc) for n, s, e, sc in ops] == [
        ("%op.1", 1000, 1002, "fit.kernel"),
        ("%op.2", 1005, 1007, xplane.NO_SCOPE),
        ("%op.3", 1009, 1011, "fit.bisect"),
        ("%op.1", 1020, 1022, "fit.kernel")]
    assert tree[xplane.HOST_PLANE]["python3#0"] == [
        (xplane.WINDOW_EVENT, 1000, 1030)]
    r = xplane.reduce(tree)
    assert r.scopes == pytest.approx({"fit.kernel": 4e-9, "fit.bisect": 2e-9,
                                      xplane.NO_SCOPE: 2e-9})


def test_recorded_scoped_chip_trace_reduces_by_scope():
    """20 ms of a traced ``weblogs194d-latest`` run recorded on one v5e, as
    ``xplane.planes`` reads it (each device op with its scope), names cut
    to 100 characters."""
    tree = _load(SCOPED_TRACE)
    r = xplane.reduce(tree)
    plain = xplane.reduce(_unscoped(tree))
    assert (r.window_s, r.busy_s, r.gaps, r.ops) == (
        plain.window_s, plain.busy_s, plain.gaps, plain.ops)
    assert r.devices == 1 and 0 < r.busy_s < r.window_s
    assert set(r.scopes) == FIT_SCOPES | {xplane.NO_SCOPE}
    assert sum(r.scopes.values()) == pytest.approx(sum(r.ops.values()),
                                                   rel=1e-12)
    assert all(name.split("|", 1)[0] in r.scopes for name, _ in r.top_ops())
    assert [n for n, _ in r.top_scopes()] == sorted(
        r.scopes, key=r.scopes.get, reverse=True)


# -------------------------------------------------------------- window numbers
def _req(issue_ms, done_ms, keys=4, error=None):
    r = driver.Request(0, np.zeros(keys, np.int32))
    r.t_issue = int(issue_ms * MS)
    r.t_done = None if done_ms is None else int(done_ms * MS)
    r.error = error
    return r


def test_window_counts_answers_inside_and_ages_open_requests():
    log = [_req(-5, 2),          # issued before, answered inside: ops only
           _req(1, 3),           # inside: 2 ms
           _req(4, 9),           # inside: 5 ms
           _req(8, 15),          # answered after the close: age 2 ms
           _req(9, None),        # never answered: age 1 ms, failed
           _req(2, 6, error="x"),  # raised: failed, no ops
           _req(10, 11)]         # issued at the close: not in the window
    w = driver.window_numbers(log, 0, 10 * MS)
    assert w["ops"] == 3 * 4
    assert w["seconds"] == pytest.approx(0.01)
    assert sorted(w["latencies_ms"]) == pytest.approx([1, 2, 2, 4, 5])
    assert w["attempted"] == 5 and w["failed"] == 2
    ops = manifest.metric_reader("ops_per_s").read
    p99 = manifest.metric_reader("request_p99_ms").read

    class Ctx:
        window = w
    assert ops(Ctx) == pytest.approx(1200.0)
    assert p99(Ctx) == pytest.approx(np.percentile([1, 2, 2, 4, 5], 99))


def test_quiet_stretches_find_the_longest_stretch_with_no_answer():
    log = [_req(0, 2), _req(1, 3), _req(3, 9), _req(4, None),
           _req(5, 7, error="x"), _req(8, 1500)]
    q = driver.quiet_stretches(log, 0, 2000 * MS)
    assert q["longest_ms"] == pytest.approx(1491)     # 9 -> 1500 ms
    assert q["at_s"] == pytest.approx(0.009)
    assert q["slowest_ms"] == pytest.approx(1492)
    assert q["ops_each_s"] == [12, 4]


def test_stall_watch_takes_the_stacks_once_per_stall():
    import threading
    import time

    class Loop:
        last_done_ns = time.perf_counter_ns()
    gate = threading.Event()
    blocked = threading.Thread(target=gate.wait, name="bench-client-3")
    blocked.start()
    watch = driver.StallWatch(Loop, after_s=0.1, most=2)
    watch.start()
    try:
        time.sleep(0.5)                   # one stall, however long
    finally:
        watch.stop()
        gate.set()
        blocked.join()
    assert len(watch.snapshots) == 1
    _, silent, stacks = watch.snapshots[0]
    assert silent >= 0.1
    assert any(w.startswith("[bench-client]") and "wait" in w
               for w in stacks)


def test_gc_pauses_time_the_collections_inside_the_window():
    import gc
    import time
    pauses = driver.GcPauses()
    try:
        t_open = time.perf_counter_ns()
        gc.collect()
        gc.collect(0)
        t_close = time.perf_counter_ns()
    finally:
        pauses.close()
    g = pauses.summary(t_open, t_close)
    assert g["per_gen"][2] >= 1 and g["per_gen"][0] >= 1
    assert 0 < g["longest_ms"] <= g["total_ms"]
    assert 0 <= g["longest_at_s"] <= (t_close - t_open) * 1e-9
    assert pauses._on_gc not in gc.callbacks
    assert pauses.summary(t_close, t_close + 1)["per_gen"] == [0, 0, 0]


def test_a_stalled_request_sets_the_tail():
    log = [_req(i, i + 1) for i in range(0, 98)] + [_req(0.5, None),
                                                    _req(0.7, 150)]
    w = driver.window_numbers(log, 0, 100 * MS)

    class Ctx:
        window = w
    assert manifest.metric_reader("request_p99_ms").read(Ctx) > 50


# -------------------------------------------------------------------- roofline
def test_roofline_bytes_are_query_answer_and_window():
    assert roofline.key_bytes("float32") == 4
    assert roofline.key_bytes("float64") == 8
    assert roofline.probe_bytes(64, 4) == 4 + 4 + 130 * 4 == 528
    assert roofline.probe_bytes(64, 8) == 8 + 4 + 130 * 8 == 1052
    assert roofline.probe_bytes(256, 4) == 4 + 4 + 514 * 4
    assert roofline.least_seconds(1_000_000, 64, 8, 819e9) == \
        pytest.approx(1052e6 / 819e9)


def test_roofline_reader_counts_ops_answered_in_the_traced_stretch():
    log = [_req(1, 2, keys=1000), _req(2, 20, keys=1000)]

    class Ctx:
        trace = xplane.Reduction(window_s=0.01, busy_s=0.001, ops={},
                                 gaps=[], devices=1)
        traced_ops_window = (0, 10 * MS)
        peaks = {"hbm_bytes_per_s": 819e9}
        config = {"error": 64, "key_dtype": "float64"}
    Ctx.log = log
    got = manifest.metric_reader("search_roofline").read(Ctx)
    assert got == pytest.approx(100 * 1000 * 1052 / 819e9 / 0.001)


# -------------------------------------------------------------------- samplers
def test_zeta_matches_ycsb_constant():
    assert zeta(10_000_000_000, 0.99) == pytest.approx(26.46902820178302,
                                                       rel=1e-9)


def test_latest_puts_four_fifths_in_the_newest_of_34_shards():
    n = 1 << 26
    s = manifest.distribution("latest").make(n, {"theta": 0.99})
    idx = s.draw(np.random.default_rng(7), 1 << 18)
    assert idx.min() >= 0 and idx.max() == n - 1
    share = np.mean(idx >= n - n // 34)
    assert share == pytest.approx(s.zipf.mass(0, n // 34), abs=0.01)
    assert 0.78 < share < 0.82


def test_zipfian_ranks_follow_theta():
    z = Zipfian(1 << 20, 0.99)
    r = z.ranks(np.random.default_rng(3), 1 << 20)
    c = np.bincount(r, minlength=8)
    assert c[0] / r.size == pytest.approx(1 / z.zetan, rel=0.02)
    assert c[1] / c[0] == pytest.approx(2 ** -0.99, rel=0.03)
    assert c[7] / c[0] == pytest.approx(8 ** -0.99, rel=0.08)


def test_scrambled_zipf_scatters_hot_keys_over_the_column():
    n = 1 << 26
    s = manifest.distribution("scrambled_zipf").make(n, {
        "theta": 0.99, "item_count": 10_000_000_000,
        "zetan": 26.46902820178302})
    idx = s.draw(np.random.default_rng(9), 1 << 18)
    assert idx.min() >= 0 and idx.max() < n
    _, counts = np.unique(idx, return_counts=True)
    assert counts.max() / idx.size == pytest.approx(1 / 26.469, rel=0.05)
    per_shard = np.bincount(idx // (n // 34 + 1), minlength=34)
    assert per_shard.min() > 0.4 * idx.size / 34     # no shard starved


def test_uniform_covers_the_column():
    idx = manifest.distribution("uniform").make(1000, {}).draw(
        np.random.default_rng(1), 100_000)
    assert idx.min() == 0 and idx.max() == 999


# -------------------------------------------------------------------- manifest
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_every_file_the_manifest_names_resolves():
    m = manifest.manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    for c in m["configs"]:
        cfg = manifest.config(c["name"])
        assert cfg["name"] == c["name"]
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert set(c["reduced"]) <= set(cfg) and cfg["reduced"] == \
            c["reduced"]
        assert callable(manifest.dataset(cfg["generator"]).generate)
    for w in m["workloads"]:
        mix = manifest.traffic(w["traffic"])
        assert mix["name"] == w["traffic"]
        assert callable(manifest.distribution(mix["distribution"]).make)
        assert w["config"] in {c["name"] for c in m["configs"]}
        e2e = [x["name"] for x in manifest.metrics_of(w["name"],
                                                      "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert manifest.metrics_of(w["name"], "per_layer")
    for kind in ("end_to_end", "per_layer"):
        for metric in m[kind]:
            assert callable(manifest.metric_reader(metric["name"]).read)
    for path in sorted((ROOT / "bench" / "configs").glob("*.json")):
        cfg = manifest.config_file(path.stem)      # cells or not
        assert cfg["name"] == path.stem and cfg["key_dtype"] in (
            "float64", "float32")
        assert callable(manifest.dataset(cfg["generator"]).generate)
    assert manifest.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        manifest.peaks("TPU v9 imaginary")


def test_manifest_keeps_the_contract_format():
    m = manifest.manifest()
    assert len(json.dumps(m)) < 64 * 1024
    assert m["command"] == ["python3", "bench/run.py"]
    assert 1 <= m["run_seconds"] <= 51
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in m[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    layers = {x["layer"] for x in m["per_layer"]}
    e2e = {x["name"] for x in m["end_to_end"]}
    for metric in m["end_to_end"] + m["per_layer"]:
        assert UNIT.match(metric["unit"]) and metric["better"] in (
            "lower", "higher")
    for metric in m["end_to_end"]:
        assert 0.01 <= metric["bound"] <= 0.25
        assert metric["source"] in ("host_clock", "device_trace")
    for metric in m["per_layer"]:
        assert metric["moves"] in e2e and metric["layer"] in layers
    for w in m["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for path in (ROOT / "bench").rglob("*"):
        rel = path.relative_to(ROOT).as_posix()
        assert "__pycache__" in rel or re.match(r"^[A-Za-z0-9_./-]+$", rel)


# --------------------------------------------------------------------- command
def test_run_refuses_a_cpu_backend_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "weblogs194d-latest",
         "--seed", str(2 ** 33 + 5), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "runs only on the chip" in out.stderr
    assert not any(line.startswith("{") for line in out.stdout.splitlines())
    assert "platform=cpu" in out.stdout
