"""Closed-loop clients and the window's end-to-end numbers.

Each client is a thread that draws a request of ``request_keys`` key
indices from its own stream (``[seed, client]``), sends the keys, waits for
the answer and sends the next; there is no think time.  Every request is
logged with its issue and answer times (host clock, ns)."""
from __future__ import annotations

import collections
import contextlib
import gc
import sys
import threading
import time
import traceback

import numpy as np


class Request:
    __slots__ = ("client", "t_issue", "t_done", "idx", "answer", "error")

    def __init__(self, client: int, idx: np.ndarray):
        self.client = client
        self.idx = idx
        self.t_issue = time.perf_counter_ns()
        self.t_done: int | None = None
        self.answer: np.ndarray | None = None
        self.error: str | None = None


class ClosedLoop:
    """``clients`` threads calling ``call(keys)`` back to back."""

    def __init__(self, call, keys: np.ndarray, sampler, seed: int, *,
                 clients: int, request_keys: int, annotate: bool = False):
        self.call = call
        self.keys = keys
        self.sampler = sampler
        self.seed = int(seed)
        self.request_keys = int(request_keys)
        self.annotate = annotate
        self.log: list[Request] = []       # list.append is atomic
        self.last_done_ns = time.perf_counter_ns()
        self._stop = threading.Event()
        self._threads = [threading.Thread(target=self._client, args=(c,),
                                          name=f"bench-client-{c}",
                                          daemon=True)
                         for c in range(clients)]

    def _client(self, c: int) -> None:
        rng = np.random.default_rng([self.seed, c])
        scope = contextlib.nullcontext
        if self.annotate:
            from jax.profiler import TraceAnnotation

            def scope():
                return TraceAnnotation("bench.request")
        while not self._stop.is_set():
            idx = self.sampler.draw(rng, self.request_keys).astype(np.int32)
            q = self.keys[idx]
            req = Request(c, idx)
            self.log.append(req)
            try:
                with scope():
                    req.answer = np.asarray(self.call(q))
            except Exception as exc:       # counted as failed, not raised
                req.error = repr(exc)
            req.t_done = self.last_done_ns = time.perf_counter_ns()

    def start(self) -> None:
        for t in self._threads:
            t.start()

    def stop(self, wait_s: float) -> bool:
        """Stop issuing; wait up to ``wait_s`` for the open requests.  True
        when every client has ended."""
        self._stop.set()
        deadline = time.monotonic() + wait_s
        for t in self._threads:
            t.join(max(0.0, deadline - time.monotonic()))
        return not any(t.is_alive() for t in self._threads)


class StallWatch:
    """Python stacks of every thread, taken once the clients have had no
    answer for ``after_s``: where a stall sits (a device read, a lock, the
    queue).  At most ``most`` stalls, one snapshot each.  The watch polls
    every 50 ms; ``late_ms``, the longest it waited past a poll, says
    whether the whole process (or its GIL) was held, and the watch with
    it."""

    def __init__(self, loop: ClosedLoop, after_s: float = 0.5,
                 most: int = 3):
        self.loop = loop
        self.after_s = after_s
        self.most = most
        self.snapshots: list[tuple[int, float, dict[str, int]]] = []
        self.late_ms = 0.0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._watch, daemon=True,
                                        name="bench-stall-watch")

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._thread.join()

    def _watch(self) -> None:
        armed = True
        last = time.perf_counter_ns()
        while not self._stop.wait(0.05):
            now = time.perf_counter_ns()
            self.late_ms = max(self.late_ms, (now - last) * 1e-6 - 50.0)
            last = now
            if (now - self.loop.last_done_ns) * 1e-9 < self.after_s:
                armed = True
            elif armed and len(self.snapshots) < self.most:
                armed = False
                self.snapshots.append((now, (now - self.loop.last_done_ns)
                                       * 1e-9, self.stacks()))

    @staticmethod
    def stacks(depth: int = 5) -> dict[str, int]:
        """Each distinct stack (innermost ``depth`` frames) of the
        threads other than the caller, with how many threads are in it."""
        me = threading.get_ident()
        names = {t.ident: t.name for t in threading.enumerate()}
        out: collections.Counter = collections.Counter()
        for ident, frame in sys._current_frames().items():
            if ident == me:
                continue
            frames = traceback.extract_stack(frame)[-depth:]
            where = " < ".join(
                f"{'/'.join(f.filename.split('/')[-2:])}:{f.lineno} "
                f"{f.name}" for f in reversed(frames))
            kind = names.get(ident, "?").rstrip("0123456789_-")
            out[f"[{kind}] {where}"] += 1
        return dict(out)


class GcPauses:
    """Collections of Python's cyclic garbage collector, timed through
    ``gc.callbacks``: a collection holds the GIL, so every client thread
    and the pipeline wait for it, and a long one stalls the window."""

    def __init__(self):
        self.events: list[tuple[int, int, int]] = []  # (gen, start, ns)
        self._start: int | None = None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        now = time.perf_counter_ns()
        if phase == "start":
            self._start = now
        elif self._start is not None:
            self.events.append((info["generation"], self._start,
                                now - self._start))
            self._start = None

    def close(self) -> None:
        gc.callbacks.remove(self._on_gc)

    def summary(self, t_open: int, t_close: int) -> dict:
        """Collections that began inside ``[t_open, t_close)``: how many
        of each generation, their ms in all, and the longest."""
        inside = [e for e in self.events if t_open <= e[1] < t_close]
        longest = max(inside, key=lambda e: e[2], default=(-1, t_open, 0))
        return {"per_gen": [sum(1 for e in inside if e[0] == g)
                            for g in range(3)],
                "total_ms": sum(e[2] for e in inside) * 1e-6,
                "longest_ms": longest[2] * 1e-6, "longest_gen": longest[0],
                "longest_at_s": (longest[1] - t_open) * 1e-9}


def window_numbers(log: list[Request], t_open: int, t_close: int) -> dict:
    """End-to-end numbers of the window ``[t_open, t_close)`` (ns):

    * ``ops``: keys answered inside the window (requests answered there);
    * ``latencies_ms``: every request issued in the window, issue to
      answer; one still open at the close counts with its age then;
    * ``attempted`` / ``failed``: requests issued in the window, and those
      of them that raised or were never answered.
    """
    ops = 0
    lat = []
    attempted = failed = 0
    for r in log:
        done = r.t_done
        if (done is not None and r.error is None
                and t_open <= done < t_close):
            ops += r.idx.size
        if t_open <= r.t_issue < t_close:
            attempted += 1
            end = t_close if done is None or done > t_close else done
            lat.append((end - r.t_issue) * 1e-6)
            if done is None or r.error is not None:
                failed += 1
    return {"ops": ops, "latencies_ms": np.asarray(lat, np.float64),
            "attempted": attempted, "failed": failed,
            "seconds": (t_close - t_open) * 1e-9}


def quiet_stretches(log: list[Request], t_open: int, t_close: int
                    ) -> dict:
    """Where the window answered nothing, to find a stall in any run: the
    longest stretch between answers (from the open, to the close), where
    it began (s after the open), the slowest request answered in the
    window, and the ops answered in each whole second of the window."""
    done = np.sort(np.fromiter(
        (r.t_done for r in log if r.t_done is not None and r.error is None
         and t_open <= r.t_done < t_close), np.int64))
    marks = np.concatenate([[t_open], done, [t_close]])
    gaps = np.diff(marks)
    at = int(np.argmax(gaps))
    slow = max(((r.t_done - r.t_issue) for r in log
                if r.t_done is not None and t_open <= r.t_done < t_close),
               default=0)
    per_s = np.zeros(max(1, int((t_close - t_open) // 1_000_000_000)),
                     np.int64)
    for r in log:
        if r.t_done is not None and r.error is None:
            s = (r.t_done - t_open) // 1_000_000_000
            if 0 <= s < per_s.size and r.t_done < t_close:
                per_s[s] += r.idx.size
    return {"longest_ms": gaps[at] * 1e-6, "at_s": (marks[at] - t_open) * 1e-9,
            "slowest_ms": slow * 1e-6, "ops_each_s": per_s.tolist()}
