"""Profiler trace -> device busy time, per-op totals and idle gaps.

The one reduction every per-layer metric that reads the trace goes through.
``jax.profiler`` writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``;
its device planes are ``/device:TPU:<i>``, whose ``XLA Ops`` line holds one
event per device operation, and ``/host:CPU`` holds one line per host thread
with the runtime's and the benchmark's own annotations.  All timestamps are
on one clock.

* busy: the union of device-op intervals inside the window, averaged over
  the device planes that ran anything;
* ops: each op name's summed time inside the window;
* gaps: the window minus the busy union, each gap labelled with the
  innermost host event that covers its midpoint (what the host was doing).
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
WINDOW_EVENT = "bench.traced_window"
NO_HOST_EVENT = "(no host event)"


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                        # mean over the devices used
    ops: dict[str, float]                # op name -> seconds
    gaps: list[tuple[str, float]]        # (host label, seconds), each gap
    devices: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def top_ops(self, k: int = 10) -> list[list]:
        return [[n, s] for n, s in
                sorted(self.ops.items(), key=lambda x: -x[1])[:k]]

    def idle_by_label(self, k: int = 10) -> list[list]:
        """Idle seconds summed by what the host was doing, largest first."""
        total: dict[str, float] = defaultdict(float)
        for label, s in self.gaps:
            total[label] += s
        return [[n, s] for n, s in
                sorted(total.items(), key=lambda x: -x[1])[:k]]


def find_trace(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def planes(path: str) -> dict[str, dict[str, list[tuple[str, float, float]]]]:
    """``{plane: {line: [(name, start_ns, end_ns), ...]}}`` of the device
    and host planes.  Host threads share line names, so each line's key is
    its name and its index on the plane."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not (plane.name.startswith(DEVICE_PREFIX)
                or plane.name == HOST_PLANE):
            continue
        out[plane.name] = {
            f"{line.name}#{i}": [(e.name, e.start_ns,
                                  e.start_ns + e.duration_ns)
                                 for e in line.events]
            for i, line in enumerate(plane.lines)}
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
    ops: dict[str, float] = defaultdict(float)
    gaps: list[tuple[str, float]] = []
    for name in sorted(p for p in tree if p.startswith(DEVICE_PREFIX)):
        events = [ev for line, evs in tree[name].items()
                  if line.rsplit("#", 1)[0] == OPS_LINE for ev in evs]
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in events
                  if e > lo and s < hi]
        if not inside:
            continue
        devices += 1
        for n, s, e in inside:
            ops[n] += (e - s) * 1e-9
        cover = union([(s, e) for _, s, e in inside])
        busy_ns += sum(e - s for s, e in cover)
        edges = [lo] + [t for se in cover for t in se] + [hi]
        idle = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
        labels = labels_at(host, [(s + e) / 2 for s, e in idle])
        gaps += [(lab, (e - s) * 1e-9) for lab, (s, e) in zip(labels, idle)]
    devices_used = max(devices, 1)
    return Reduction(window_s=span * 1e-9,
                     busy_s=busy_ns * 1e-9 / devices_used,
                     ops={k: v / devices_used for k, v in ops.items()},
                     gaps=gaps, devices=devices)
