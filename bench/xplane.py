"""Profiler trace -> device busy time, per-op totals and idle gaps.

The one reduction every per-layer metric that reads the trace goes through.
``jax.profiler`` writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``;
its device planes are ``/device:TPU:<i>``, whose ``XLA Ops`` line holds one
event per device operation, and ``/host:CPU`` holds one line per host thread
with the runtime's and the benchmark's own annotations.  All timestamps are
on one clock.  A device op's scope path is the ``tf_op`` stat of its event
metadata, which ``ProfileData`` does not show: ``planes`` reads it with the
protobuf classes installed with TensorFlow, without importing TensorFlow.

* busy: the union of device-op intervals inside the window, averaged over
  the device planes that ran anything;
* ops: each op name's summed time inside the window;
* scopes: the same time summed by each op's innermost ``fit.*`` name scope
  (``(no scope)`` for ops without one, such as control flow, whose time
  overlaps that of the ops they run), so scopes and ops sum alike;
* gaps: the window minus the busy union, each gap labelled with the
  innermost host event that covers its midpoint (what the host was doing).
"""
from __future__ import annotations

import dataclasses
import functools
import glob
import importlib.util
import os
import pathlib
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
WINDOW_EVENT = "bench.traced_window"
NO_HOST_EVENT = "(no host event)"
SCOPE_STAT = "tf_op"
SCOPE_PREFIX = "fit."
NO_SCOPE = "(no scope)"


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                        # mean over the devices used
    ops: dict[str, float]                # op name -> seconds
    gaps: list[tuple[str, float]]        # (host label, seconds), each gap
    devices: int
    scoped_ops: dict[tuple[str, str], float] = dataclasses.field(
        default_factory=dict)            # (scope, op name) -> seconds

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    @property
    def scopes(self) -> dict[str, float]:
        """Device seconds per scope."""
        total: dict[str, float] = defaultdict(float)
        for (scope, _), s in self.scoped_ops.items():
            total[scope] += s
        return dict(total)

    def top_ops(self, k: int = 10) -> list[list]:
        """The ops that took most device time, each as ``<scope>|<op>``."""
        return _largest({f"{sc}|{n}": s
                         for (sc, n), s in self.scoped_ops.items()}, k)

    def top_scopes(self, k: int = 10) -> list[list]:
        return _largest(self.scopes, k)

    def idle_by_label(self, k: int = 10) -> list[list]:
        """Idle seconds summed by what the host was doing, largest first."""
        total: dict[str, float] = defaultdict(float)
        for label, s in self.gaps:
            total[label] += s
        return _largest(total, k)


def _largest(seconds: dict[str, float], k: int) -> list[list]:
    return [[n, s] for n, s in
            sorted(seconds.items(), key=lambda x: -x[1])[:k]]


def find_trace(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


@functools.lru_cache(maxsize=1)
def _xplane_pb2():
    """TensorFlow's ``xplane_pb2``, loaded from its file alone."""
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.submodule_search_locations:
        raise ImportError("reading device-op scopes needs the xplane_pb2 "
                          "module installed with tensorflow")
    path = (pathlib.Path(spec.submodule_search_locations[0])
            / "tsl" / "profiler" / "protobuf" / "xplane_pb2.py")
    module_spec = importlib.util.spec_from_file_location("xplane_pb2", path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return module


def innermost_scope(path: str) -> str:
    """The last ``fit.*`` part of a ``/``-separated scope path."""
    for part in reversed(path.split("/")):
        if part.startswith(SCOPE_PREFIX):
            return part
    return NO_SCOPE


def device_scopes(data: bytes) -> dict[str, list[list[tuple[str, str]]]]:
    """``{plane: [[(event name, scope) per event] per line]}`` of the
    device planes of a serialized XSpace, in the trace's own order."""
    space = _xplane_pb2().XSpace()
    space.ParseFromString(data)
    out = {}
    for plane in space.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        stat = next((k for k, m in plane.stat_metadata.items()
                     if m.name == SCOPE_STAT), None)
        meta = {k: (m.name, innermost_scope(next(
                    (s.str_value for s in m.stats if s.metadata_id == stat),
                    ""))) for k, m in plane.event_metadata.items()}
        out[plane.name] = [[meta[e.metadata_id] for e in line.events]
                           for line in plane.lines]
    return out


def planes(path: str) -> dict[str, dict[str, list[tuple]]]:
    """``{plane: {line: [(name, start_ns, end_ns), ...]}}`` of the device
    and host planes, a device op's tuple ending in its scope
    (``innermost_scope``).  Host threads share line names, so each line's
    key is its name and its index on the plane."""
    from jax.profiler import ProfileData

    data = pathlib.Path(path).read_bytes()
    scopes = device_scopes(data)
    out = {}
    for plane in ProfileData.from_serialized_xspace(data).planes:
        if plane.name == HOST_PLANE:
            out[plane.name] = {
                f"{line.name}#{i}": [(e.name, e.start_ns,
                                      e.start_ns + e.duration_ns)
                                     for e in line.events]
                for i, line in enumerate(plane.lines)}
        elif plane.name.startswith(DEVICE_PREFIX):
            out[plane.name] = {
                f"{line.name}#{i}": _with_scopes(line.events, named)
                for i, (line, named) in enumerate(
                    zip(plane.lines, scopes[plane.name], strict=True))}
    return out


def _with_scopes(events, named: list[tuple[str, str]]) -> list[tuple]:
    out = []
    for e, (name, scope) in zip(events, named, strict=True):
        if e.name != name:
            raise ValueError(f"the trace's events disagree: {e.name!r} "
                             f"read as {name!r}")
        out.append((e.name, e.start_ns, e.start_ns + e.duration_ns, scope))
    return out


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted disjoint cover of the given intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def window_of(host: dict[str, list]) -> tuple[float, float]:
    """The benchmark's ``bench.traced_window`` annotation."""
    for events in host.values():
        for name, s, e in events:
            if name == WINDOW_EVENT:
                return s, e
    raise ValueError(f"no {WINDOW_EVENT!r} event on the host plane")


def labels_at(host: dict[str, list], times: list[float]) -> list[str]:
    """For each time, the shortest host event covering it (the window's own
    annotation excepted): one sweep over the events sorted by start."""
    events = sorted((s, e, name) for evs in host.values()
                    for name, s, e in evs if name != WINDOW_EVENT)
    order = sorted(range(len(times)), key=times.__getitem__)
    out = [NO_HOST_EVENT] * len(times)
    active: list[tuple[float, float, str]] = []
    i = 0
    for k in order:
        t = times[k]
        while i < len(events) and events[i][0] <= t:
            active.append(events[i])
            i += 1
        active = [ev for ev in active if ev[1] > t]
        if active:
            out[k] = min(active, key=lambda ev: ev[1] - ev[0])[2]
    return out


def reduce(tree: dict, window: tuple[float, float] | None = None
           ) -> Reduction:
    """Reduce ``planes(path)``'s output over ``window`` (ns on the trace's
    clock; default: the ``bench.traced_window`` annotation)."""
    host = tree.get(HOST_PLANE, {})
    lo, hi = window if window is not None else window_of(host)
    span = hi - lo
    busy_ns, devices = 0.0, 0
    scoped: dict[tuple[str, str], float] = defaultdict(float)
    gaps: list[tuple[str, float]] = []
    for name in sorted(p for p in tree if p.startswith(DEVICE_PREFIX)):
        events = [ev for line, evs in tree[name].items()
                  if line.rsplit("#", 1)[0] == OPS_LINE for ev in evs]
        inside = [(n, max(s, lo), min(e, hi), *scope)
                  for n, s, e, *scope in events if e > lo and s < hi]
        if not inside:
            continue
        devices += 1
        for n, s, e, *scope in inside:
            scoped[scope[0] if scope else NO_SCOPE, n] += (e - s) * 1e-9
        cover = union([(s, e) for _, s, e, *_ in inside])
        busy_ns += sum(e - s for s, e in cover)
        edges = [lo] + [t for se in cover for t in se] + [hi]
        idle = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
        labels = labels_at(host, [(s + e) / 2 for s, e in idle])
        gaps += [(lab, (e - s) * 1e-9) for lab, (s, e) in zip(labels, idle)]
    devices_used = max(devices, 1)
    scoped = {k: v / devices_used for k, v in scoped.items()}
    ops: dict[str, float] = defaultdict(float)
    for (_, n), s in scoped.items():
        ops[n] += s
    return Reduction(window_s=span * 1e-9,
                     busy_s=busy_ns * 1e-9 / devices_used,
                     ops=dict(ops), gaps=gaps, devices=devices,
                     scoped_ops=scoped)
