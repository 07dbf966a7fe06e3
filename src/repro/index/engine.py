"""`LookupEngine`: one bounded-window search implementation per backend.

The paper's hot path -- route, interpolate, binary-search the +-error window
-- used to be hand-rolled four times (host tree, XLA index, Pallas wrapper,
sharded serving).  It now exists exactly once per backend, behind a registry:

    numpy       host vectorized bounded bisect over the f64 key column
    xla-window  gather the 2e+2 window and compare-reduce (VPU friendly)
    xla-bisect  log2(2e) halving steps of single gathers (fewer bytes, big e)
    pallas      bucketed compare-reduce TPU kernel with XLA-bisect fallback
                (compiled by Mosaic on TPU, interpreted on the CPU backend)

``make_engine(table, backend=...)`` returns an engine whose ``lookup`` maps a
query batch to global ranks (-1 if absent; the *leftmost* rank for duplicated
keys -- every backend snaps a hit whose left neighbour equals the query to
the run start, see ``snap_leftmost``, so ranks are segmentation-independent).
Every backend also implements the typed query plane's primitive
``search(queries, side="left"|"right")`` -- the same bounded-window machinery
generalized to insertion ranks (``np.searchsorted`` semantics, with
``snap_side`` repairing duplicate runs that extend past the window) -- from
which ``repro.index.query`` derives point / range / count / predecessor /
successor uniformly across backends.
Backends return identical ranks for any key column whose keys and queries
are exact in f32 (e.g. integer keys < 2^24, the serving regime -- see
rescale_keys): the ``numpy`` backend compares in f64 while the device
backends compare in f32, so a query that is only f32-equal to a stored key
can differ in membership across that boundary.  ``DeviceIndex`` is the f32 device form of a
``SegmentTable`` (re-exported by repro.core.jax_index for compatibility).
"""
from __future__ import annotations

import functools
from typing import Callable, Literal, NamedTuple, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.contracts import hot_path
from repro.analysis.sanitizer import make_lock

from .query import QueryVerbs
from .table import SegmentTable, numpy_lookup, numpy_search
from .telemetry import CH_D2H, CH_H2D, CH_LAUNCH, CH_TIER_PREFIX, NO_SPAN


def _bucket_size(n: int) -> int:
    """The power-of-two batch bucket ``n`` pads into (floor 16, so tiny
    batches share a handful of shapes instead of one each)."""
    return max(16, 1 << (int(n) - 1).bit_length())


class DeviceIndex(NamedTuple):
    """f32/i32 device form of a SegmentTable (arrays VMEM/HBM friendly)."""
    seg_start: jax.Array  # (S,) f32  first key of each segment
    slope: jax.Array      # (S,) f32
    base: jax.Array       # (S,) i32  global position of segment start
    seg_end: jax.Array    # (S,) i32  one past the segment end
    keys: jax.Array       # (N,) f32  the sorted key column (HBM resident)
    error: int            # static


def device_index(table: SegmentTable) -> DeviceIndex:
    """Convert (and cache on the table -- snapshots are shared by engines)."""
    dev = getattr(table, "_device_cache", None)
    if dev is None:
        # cast on the host: a device-side cast compiles once per shape
        def put(a, dtype):
            return jax.device_put(np.asarray(a, dtype))

        dev = DeviceIndex(
            seg_start=put(table.start_key, np.float32),
            slope=put(table.slope, np.float32),
            base=put(table.base, np.int32),
            seg_end=put(table.seg_end, np.int32),
            keys=put(table.keys, np.float32),
            error=int(table.error),
        )
        object.__setattr__(table, "_device_cache", dev)  # frozen dataclass
    return dev


# --------------------------------------------------------------------- device
def snap_leftmost(keys: jax.Array, queries: jax.Array, rank: jax.Array,
                  hit: jax.Array) -> jax.Array:
    """Snap duplicate hits to the leftmost occurrence (device mirror of the
    ``numpy_lookup`` fix): when a found rank's left neighbour still equals
    the query, the duplicate run straddles a segment boundary and the
    window search returned an in-segment rank.  ``lax.cond`` skips the
    full-column bisect entirely unless some query actually needs it, so the
    duplicate-free fast path pays one extra gather."""
    need = hit & (rank > 0) & (keys[jnp.maximum(rank - 1, 0)] == queries)
    fixed = jax.lax.cond(
        jnp.any(need),
        lambda: jnp.searchsorted(keys, queries, side="left").astype(rank.dtype),
        lambda: rank)
    return jnp.where(need, fixed, rank)


def snap_side(keys: jax.Array, queries: jax.Array, rank: jax.Array,
              side: str) -> jax.Array:
    """Side-generalized duplicate snap for insertion-rank searches (the
    ``search`` primitive): a bounded window parks inside a duplicate run that
    extends past it, which is detectable from the landing position alone --
    for ``side="left"`` the left neighbour still equals the query, for
    ``side="right"`` the landing key itself does.  ``lax.cond`` skips the
    full-column searchsorted unless some query actually needs it (the same
    fast-path discipline as :func:`snap_leftmost`)."""
    n = keys.shape[0]
    if side == "left":
        need = (rank > 0) & (keys[jnp.maximum(rank - 1, 0)] == queries)
    else:
        need = (rank < n) & (keys[jnp.minimum(rank, n - 1)] == queries)
    fixed = jax.lax.cond(
        jnp.any(need),
        lambda: jnp.searchsorted(keys, queries, side=side).astype(rank.dtype),
        lambda: rank)
    return jnp.where(need, fixed, rank)


def predict_positions(idx: DeviceIndex, queries: jax.Array) -> jax.Array:
    """Interpolated (approximate) global positions; error <= idx.error by Eq. 1.

    Device mirror of SegmentTable.predict: route, FMA, clamp into the owning
    segment's position range so inter-segment gap queries cannot overshoot."""
    sid = jnp.clip(jnp.searchsorted(idx.seg_start, queries, side="right") - 1,
                   0, idx.seg_start.shape[0] - 1)
    local = (queries - idx.seg_start[sid]) * idx.slope[sid]
    pred = idx.base[sid] + jnp.round(local).astype(jnp.int32)
    return jnp.clip(pred, idx.base[sid], idx.seg_end[sid])


def xla_lookup(idx: DeviceIndex, queries: jax.Array,
               strategy: Literal["window", "bisect"] = "window") -> jax.Array:
    """Batched point lookup, rank or -1.  jit-safe; ``error`` is static.
    Its device steps carry the ``fit.prelude``, ``fit.bisect`` and
    ``fit.snap`` named scopes."""
    n = idx.keys.shape[0]
    with jax.named_scope("fit.prelude"):
        pred = predict_positions(idx, queries)
    e = idx.error
    if strategy == "window":
        w = 2 * e + 2
        start = jnp.clip(pred - e, 0, jnp.maximum(n - w, 0)).astype(jnp.int32)
        offs = start[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]
        vals = idx.keys[jnp.minimum(offs, n - 1)]
        lt = (vals < queries[:, None]).sum(axis=1).astype(jnp.int32)
        rank = start + lt
        hit = (vals == queries[:, None]).any(axis=1)
        with jax.named_scope("fit.snap"):
            rank = snap_leftmost(idx.keys, queries, rank, hit)
            return jnp.where(hit, rank, -1)
    # bisect: lo/hi halving on the clipped window
    steps = int(np.ceil(np.log2(2 * e + 2)))

    def body(_, lh):
        lo, hi = lh
        mid = (lo + hi) // 2
        v = idx.keys[jnp.minimum(mid, n - 1)]
        go = (v < queries) & (lo < hi)
        return jnp.where(go, mid + 1, lo), jnp.where(go, hi, mid)

    with jax.named_scope("fit.bisect"):
        lo = jnp.clip(pred - e, 0, n).astype(jnp.int32)
        hi = jnp.clip(pred + e + 1, 0, n).astype(jnp.int32)
        lo, hi = jax.lax.fori_loop(0, steps, body, (lo, hi))
        ok = (lo < n) & (idx.keys[jnp.minimum(lo, n - 1)] == queries)
    with jax.named_scope("fit.snap"):
        lo = snap_leftmost(idx.keys, queries, lo, ok)
        return jnp.where(ok, lo, -1)


def xla_search(idx: DeviceIndex, queries: jax.Array, side: str = "left",
               strategy: Literal["window", "bisect"] = "bisect") -> jax.Array:
    """Batched bounded-window rank search: the device mirror of
    :func:`repro.index.table.numpy_search` (f32 compares).  Returns the
    insertion rank of every query -- ``searchsorted(keys, q, side)`` -- via
    the interpolated +-error window; jit-safe, ``error``/``side``/``strategy``
    static.

    ``window`` counts the in-window keys strictly below (``side="left"``) or
    at-or-below (``side="right"``) each query; ``bisect`` runs log2(2e+2)
    halving steps with the side's comparison.  Both end with
    :func:`snap_side`, so duplicate runs extending past the window still
    resolve to the exact global rank."""
    if side not in ("left", "right"):
        raise ValueError(f"side must be 'left' or 'right', got {side!r}")
    n = idx.keys.shape[0]
    with jax.named_scope("fit.prelude"):
        pred = predict_positions(idx, queries)
    e = idx.error
    if strategy == "window":
        w = 2 * e + 2
        start = jnp.clip(pred - e, 0, jnp.maximum(n - w, 0)).astype(jnp.int32)
        offs = start[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]
        valid = offs < n                       # clamped gathers replicate the
        vals = idx.keys[jnp.minimum(offs, n - 1)]  # last key: mask them out
        if side == "left":
            cmp = vals < queries[:, None]
        else:
            cmp = vals <= queries[:, None]
        rank = start + (valid & cmp).sum(axis=1).astype(jnp.int32)
        with jax.named_scope("fit.snap"):
            return snap_side(idx.keys, queries, rank, side)
    steps = int(np.ceil(np.log2(2 * e + 2)))

    def body(_, lh):
        lo, hi = lh
        mid = (lo + hi) // 2
        v = idx.keys[jnp.minimum(mid, n - 1)]
        ok = (v < queries) if side == "left" else (v <= queries)
        go = ok & (lo < hi)
        return jnp.where(go, mid + 1, lo), jnp.where(go, hi, mid)

    with jax.named_scope("fit.bisect"):
        lo = jnp.clip(pred - e, 0, n).astype(jnp.int32)
        hi = jnp.clip(pred + e + 1, 0, n).astype(jnp.int32)
        lo, _ = jax.lax.fori_loop(0, steps, body, (lo, hi))
    with jax.named_scope("fit.snap"):
        return snap_side(idx.keys, queries, lo, side)


# --------------------------------------------------------------------- pallas
def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


class LookupPlan(NamedTuple):
    """Static kernel geometry for a (N, error) pair."""
    kb: int         # key block size
    window: int     # 2*error + 2
    n_blocks: int
    n_pad: int


def make_plan(n_keys: int, error: int) -> LookupPlan:
    # lazy: repro.kernels imports this module for its thin wrappers
    from repro.kernels.fitting_lookup import ROWS

    window = 2 * error + 2
    kb = max(128, _round_up(window, 128))
    # whole kernel grid steps: ROWS key blocks each
    n_pad = _round_up(max(n_keys, kb), ROWS * kb)
    return LookupPlan(kb=kb, window=window, n_blocks=n_pad // kb, n_pad=n_pad)


def pad_keys(keys: jax.Array, plan: LookupPlan) -> jax.Array:
    pad = plan.n_pad - keys.shape[0]
    return jnp.pad(keys.astype(jnp.float32), (0, pad), constant_values=jnp.inf)


class BucketSlots(NamedTuple):
    """Where :func:`_pallas_bucketize` put each query.  The ``i``-th query in
    block order is ``queries[order[i]]``; it sits in bucket ``(blk[i],
    slot[i])`` when ``ok[i]``, and otherwise overflowed its bucket (its
    ``slot`` is then ``qcap``, one past the row)."""
    order: jax.Array  # (nq,) i32  query index, in block order
    blk: jax.Array    # (nq,) i32  key block the query's window starts in
    slot: jax.Array   # (nq,) i32  column in that block's bucket row
    ok: jax.Array     # (nq,) bool the query was bucketed


# the read-back's mark for a query its bucket could not hold (ranks >= -1)
_UNSET = np.iinfo(np.int32).min


def _pallas_bucketize(idx: DeviceIndex, queries: jax.Array, plan: LookupPlan,
                      qcap: int) -> tuple[jax.Array, jax.Array, BucketSlots]:
    """The XLA prelude shared by :func:`pallas_lookup` and
    :func:`pallas_search`: router + interpolation -> window starts -> queries
    bucketed by the key block their window starts in.  Returns ``(q_b,
    qlo_b, slots)``: per-block query values (+inf filler), global window
    starts, and each query's :class:`BucketSlots` coordinates, which the
    caller reads its answers back at.  A query not ``ok`` overflowed its
    bucket and must be answered by the caller's fallback."""
    nq = queries.shape[0]
    pred = predict_positions(idx, queries)
    qlo = jnp.clip(pred - idx.error, 0, plan.n_pad - plan.window).astype(jnp.int32)
    blk = qlo // plan.kb                                    # owning key block
    order = jnp.argsort(blk, stable=True).astype(jnp.int32)
    blk_s = blk[order]
    slot = jnp.arange(nq, dtype=jnp.int32) - jnp.searchsorted(
        blk_s, blk_s, side="left").astype(jnp.int32)        # rank within bucket
    ok = slot < qcap
    slot = jnp.where(ok, slot, qcap)     # overflow: off the row, so dropped
    q_b = jnp.full((plan.n_blocks, qcap), jnp.inf, jnp.float32)
    qlo_b = jnp.zeros((plan.n_blocks, qcap), jnp.int32)
    q_b = q_b.at[blk_s, slot].set(queries[order], mode="drop")
    qlo_b = qlo_b.at[blk_s, slot].set(qlo[order], mode="drop")
    return q_b, qlo_b, BucketSlots(order, blk_s, slot, ok)


def _read_back(slots: BucketSlots, ans: jax.Array) -> jax.Array:
    """``ans`` (one answer per query in block order, gathered at its
    clipped slot) back in query order, ``_UNSET`` where the query
    overflowed: one unique-index scatter of nq."""
    ans = jnp.where(slots.ok, ans, _UNSET)
    return jnp.zeros_like(ans).at[slots.order].set(ans, unique_indices=True)


def pallas_lookup(idx: DeviceIndex, queries: jax.Array, *, qcap: int = 256,
                  fallback: bool = True) -> jax.Array:
    """Batched point lookup via the Pallas kernel.  Returns ranks (-1 absent).

    XLA prelude (router + interpolation + bucketing) -> Pallas compare-reduce
    kernel -> each query's rank and hit read at its own bucket slot (nq
    gathers, put back in query order) + bisect fallback for bucket overflow.
    ``idx.error`` must be a Python int (it sizes the kernel window): jit this
    with the index arrays as arguments and ``error`` static, as the engines
    do.  The three steps carry the ``fit.prelude``, ``fit.kernel`` and
    ``fit.snap`` named scopes, the overflow fallback ``fit.bisect``."""
    # lazy: repro.kernels imports this module for its thin wrappers
    from repro.kernels.fitting_lookup import fitting_lookup_pallas

    plan = make_plan(int(idx.keys.shape[0]), int(idx.error))
    queries = queries.astype(jnp.float32)
    with jax.named_scope("fit.prelude"):
        keys_padded = pad_keys(idx.keys, plan)
        q_b, qlo_b, slots = _pallas_bucketize(idx, queries, plan, qcap)

    # --- Pallas kernel over key blocks
    with jax.named_scope("fit.kernel"):
        rank_b, found_b = fitting_lookup_pallas(
            keys_padded, q_b, qlo_b, kb=plan.kb, window=plan.window)

    # --- read back at the queries' own slots
    with jax.named_scope("fit.snap"):
        at = (slots.blk, slots.slot)
        hit = found_b.at[at].get(mode="clip")
        res = _read_back(slots, jnp.where(hit, rank_b.at[at].get(mode="clip"),
                                          -1))
        need = res == _UNSET                     # bucket-overflow queries
        res = jnp.where(need, -1, res)

    if fallback:
        # bucket-overflow queries answered by the XLA bisect path; lax.cond
        # skips the work entirely when nothing overflowed.
        with jax.named_scope("fit.bisect"):
            fb = jax.lax.cond(jnp.any(need),
                              lambda: xla_lookup(idx, queries, "bisect"),
                              lambda: res)
        res = jnp.where(need, fb, res)
    with jax.named_scope("fit.snap"):
        return snap_leftmost(idx.keys, queries, res, res >= 0)


def pallas_search(idx: DeviceIndex, queries: jax.Array, side: str = "left", *,
                  qcap: int = 256) -> jax.Array:
    """Batched insertion-rank search via the Pallas compare-reduce kernel.

    Same XLA prelude (router + interpolation + bucketing), kernel geometry
    and read-back at each query's bucket slot as :func:`pallas_lookup`; the
    kernel's masked compare-reduce simply counts with the side's comparison
    (``<`` for left, ``<=`` for right) so ``rank = window_start + count`` is
    the searchsorted insertion rank.  Bucket-overflow queries fall back to
    the XLA bisect search; the final :func:`snap_side` resolves duplicate
    runs extending past the window."""
    if side not in ("left", "right"):
        raise ValueError(f"side must be 'left' or 'right', got {side!r}")
    # lazy: repro.kernels imports this module for its thin wrappers
    from repro.kernels.fitting_lookup import fitting_lookup_pallas

    plan = make_plan(int(idx.keys.shape[0]), int(idx.error))
    queries = queries.astype(jnp.float32)
    with jax.named_scope("fit.prelude"):
        keys_padded = pad_keys(idx.keys, plan)
        q_b, qlo_b, slots = _pallas_bucketize(idx, queries, plan, qcap)

    with jax.named_scope("fit.kernel"):
        rank_b, _ = fitting_lookup_pallas(
            keys_padded, q_b, qlo_b, kb=plan.kb, window=plan.window,
            side=side)

    with jax.named_scope("fit.snap"):
        res = _read_back(slots, rank_b.at[slots.blk, slots.slot].get(
            mode="clip"))
    need = res == _UNSET                         # bucket-overflow queries
    with jax.named_scope("fit.bisect"):
        fb = jax.lax.cond(jnp.any(need),
                          lambda: xla_search(idx, queries, side, "bisect"),
                          lambda: res)
    res = jnp.where(need, fb, res)
    with jax.named_scope("fit.snap"):
        return snap_side(idx.keys, queries, res, side)


# ------------------------------------------------------------------- registry
@runtime_checkable
class LookupEngine(Protocol):
    """A compiled lookup path over one immutable SegmentTable snapshot.

    Every registered backend also implements the query plane's primitive
    ``search(queries, side)`` (insertion ranks) and, via the
    :class:`repro.index.query.QueryVerbs` mixin, the typed verbs derived
    from it (``point`` / ``range`` / ``count`` / ``predecessor`` /
    ``successor``)."""
    backend: str
    table: SegmentTable

    def lookup(self, queries) -> np.ndarray:
        """Global rank of each query, -1 if absent (host array out)."""
        ...

    def search(self, queries, side: str = "left") -> np.ndarray:
        """``searchsorted(keys, queries, side)`` insertion ranks (host array
        out): the one primitive every typed query verb derives from."""
        ...


_BACKENDS: dict[str, Callable[..., LookupEngine]] = {}


def register_backend(name: str):
    def deco(cls):
        cls.backend = name
        _BACKENDS[name] = cls
        return cls
    return deco


def available_backends() -> list[str]:
    return sorted(_BACKENDS)


def make_engine(table: SegmentTable, backend: str = "numpy", **opts) -> LookupEngine:
    """The one constructor every layer (ops, distributed, serving, benchmarks)
    goes through to get a lookup path."""
    try:
        cls = _BACKENDS[backend]
    except KeyError:
        raise ValueError(f"unknown backend {backend!r}; "
                         f"available: {available_backends()}") from None
    return cls(table, **opts)


def _prewarm_queries(table: SegmentTable, size: int) -> np.ndarray:
    """A representative warm-up batch: real keys cycled to ``size`` (real
    keys exercise the same routing/window paths production queries will)."""
    sample = np.asarray(table.keys[: min(table.n_keys, size)], np.float64)
    return np.resize(sample, size)


@register_backend("numpy")
class NumpyEngine(QueryVerbs):
    def __init__(self, table: SegmentTable):
        self.table = table
        self.fn = functools.partial(numpy_lookup, table)

    def lookup(self, queries) -> np.ndarray:
        return self.fn(queries)

    def search(self, queries, side: str = "left") -> np.ndarray:
        return numpy_search(self.table, queries, side)

    def prewarm(self, batch_sizes=None) -> None:
        """No-op: the host path has nothing to compile."""


@functools.partial(jax.jit, static_argnames=("impl", "error", "opts"))
def _run_on_index(arrays, queries, *, impl, error, opts):
    """The one jitted entry of every device engine: ``impl(DeviceIndex,
    queries, **opts)`` with the table's arrays as *arguments*, so an
    executable serves every table of the same shapes instead of carrying the
    key column as a constant.  ``error`` sizes the window and ``opts``
    (sorted ``(name, value)`` pairs: strategy, side, qcap) pick the code
    path, so both are static."""
    return impl(DeviceIndex(*arrays, error), queries, **dict(opts))


class _DeviceEngine(QueryVerbs):
    """Shared scaffolding: convert the table once, run the backend's
    ``_lookup_impl`` / ``_search_impl`` through :func:`_run_on_index`.

    Each batch is padded to its power-of-two bucket (:func:`_bucket_size`;
    padding lanes repeat the batch, so they spread over the key blocks like
    the real queries) and the tail is sliced off: a sharded service splits
    every batch by routing, and without the bucket each distinct per-shard
    size would be a fresh compile.

    ``monitor`` (a ``repro.index.telemetry.Monitor``) splits each call into
    three spans: ``engine.h2d`` (bucket padding and the host-to-device
    copy), ``engine.launch`` (the jitted call until it returns) and
    ``engine.d2h`` (the blocking read of the answer)."""

    _lookup_impl: Callable
    _search_impl: Callable

    def __init__(self, table: SegmentTable, lookup_opts: dict | None = None,
                 search_opts: dict | None = None, monitor=None):
        self.table = table
        self.index = device_index(table)
        self.monitor = monitor
        self._arrays = tuple(self.index)[:5]
        self._lookup_opts = tuple(sorted((lookup_opts or {}).items()))
        self._search_opts = dict(search_opts or {})

    def _run(self, impl, queries, opts) -> np.ndarray:
        mon = self.monitor
        with (NO_SPAN if mon is None else mon.span(CH_H2D)):
            q = np.asarray(queries, np.float32)
            flat = q.ravel()
            n = flat.size
            if n:
                flat = np.resize(flat, _bucket_size(n))
            dq = jnp.asarray(flat)
        with (NO_SPAN if mon is None else mon.span(CH_LAUNCH)):
            out = _run_on_index(self._arrays, dq, impl=impl,
                                error=self.index.error, opts=opts)
        with (NO_SPAN if mon is None else mon.span(CH_D2H)):
            out = np.asarray(out)
        return out[:n].reshape(q.shape)

    def lookup(self, queries) -> np.ndarray:
        if self.table.n_keys == 0:   # gathers on a 0-length device array are
            q = np.asarray(queries)  # undefined; an empty table always misses
            return np.full(q.shape, -1, np.int64)
        return self._run(type(self)._lookup_impl, queries, self._lookup_opts)

    def search(self, queries, side: str = "left") -> np.ndarray:
        if side not in ("left", "right"):
            raise ValueError(f"side must be 'left' or 'right', got {side!r}")
        if self.table.n_keys == 0:   # empty table: every rank is 0
            return np.zeros(np.asarray(queries).shape, np.int64)
        opts = tuple(sorted({**self._search_opts, "side": side}.items()))
        out = self._run(type(self)._search_impl, queries, opts)
        return out.astype(np.int64)

    def prewarm(self, batch_sizes=None) -> None:
        """Trace + compile the lookup and both search sides now, at the
        given batch sizes (jit caches are shape-specialized: a compile only
        helps batches of the same bucket).  Default one representative size."""
        if self.table.n_keys == 0:
            return
        for size in batch_sizes or (256,):
            q = _prewarm_queries(self.table, int(size))
            self.lookup(q)
            self.search(q, "left")
            self.search(q, "right")


@register_backend("xla-window")
class XlaWindowEngine(_DeviceEngine):
    _lookup_impl = xla_lookup
    _search_impl = xla_search

    def __init__(self, table: SegmentTable, *, monitor=None):
        super().__init__(table, {"strategy": "window"}, {"strategy": "window"},
                         monitor)


@register_backend("xla-bisect")
class XlaBisectEngine(_DeviceEngine):
    _lookup_impl = xla_lookup
    _search_impl = xla_search

    def __init__(self, table: SegmentTable, *, monitor=None):
        super().__init__(table, {"strategy": "bisect"}, {"strategy": "bisect"},
                         monitor)


@register_backend("pallas")
class PallasEngine(_DeviceEngine):
    _lookup_impl = pallas_lookup
    _search_impl = pallas_search

    def __init__(self, table: SegmentTable, *, qcap: int = 256,
                 fallback: bool = True, monitor=None):
        super().__init__(table, {"qcap": qcap, "fallback": fallback},
                         {"qcap": qcap}, monitor)


@register_backend("dispatch")
class DispatchEngine(QueryVerbs):
    """Batch-size-aware backend dispatch over one snapshot.

    The backends trade fixed cost against per-query cost: numpy wins for tiny
    probes (no device round trip), the XLA bisect wins for medium batches
    (log2(2e) gathers amortize the launch), and the Pallas plan/bucketing path
    wins for large fan-out (compare-reduce over VMEM-resident key blocks).
    ``DispatchEngine`` routes each ``lookup`` batch to the tier its size puts
    it in:

        size <= small_max          -> ``small``   (default numpy)
        small_max < size < large_min -> ``medium`` (default xla-bisect)
        size >= large_min          -> ``large``    (default pallas)

    Tier engines are built lazily on first use and cached for the lifetime of
    this engine (i.e. of the snapshot), so a serving handle swap retires them
    together with the table.  Every tier returns identical ranks for exact-f32
    workloads (see the module docstring), so dispatch is semantics-preserving.

    ``small_max``/``large_min`` default to ``None``: the thresholds are then
    derived from the Sec. 6 cost model for *this table's* error and segment
    count (:func:`repro.core.cost_model.dispatch_thresholds` -- the batch
    sizes where the modeled per-tier latency curves cross), so the breakpoints
    track the data instead of being magic constants.  Pass explicit values to
    pin them (e.g. from a measured sweep or an ``IndexPlan``).

    ``monitor`` (a ``repro.index.telemetry.Monitor``) turns on per-tier
    telemetry: every routed ``lookup``/``search`` is a span on the
    ``tier.<small|medium|large>`` channel whose rows lead with ``(batch_size,
    wall_ns)``, exactly the sample shape ``repro.core.cost_model.
    fit_tier_curves`` re-fits the tier cost curves from.  The monitor is
    also handed to the device tier engines, whose ``engine.*`` spans nest
    under the tier's.  ``None`` (the default) keeps the hot path
    record-free.
    """

    def __init__(self, table: SegmentTable, *, small_max: int | None = None,
                 large_min: int | None = None, small: str = "numpy",
                 medium: str = "xla-bisect", large: str = "pallas",
                 engine_opts: dict[str, dict] | None = None,
                 monitor=None):
        if small_max is None and large_min is None:
            # lazy: keep jax-module import light; cost_model is numpy-only
            from repro.core.cost_model import dispatch_thresholds
            small_max, large_min = dispatch_thresholds(table.error,
                                                       table.n_segments)
        if small_max is None or large_min is None:
            raise ValueError("pass both small_max and large_min, or neither "
                             "(None defers both to the cost model)")
        if not 0 <= small_max < large_min:
            raise ValueError(f"need 0 <= small_max < large_min, got "
                             f"{small_max=} {large_min=}")
        for tier in (small, medium, large):
            if tier == "dispatch":
                raise ValueError("dispatch cannot delegate to itself")
        self.table = table
        self.small_max = int(small_max)
        self.large_min = int(large_min)
        self.tiers = {"small": small, "medium": medium, "large": large}
        self.monitor = monitor
        self._engine_opts = engine_opts or {}
        self._engines: dict[str, LookupEngine] = {}
        self._lock = make_lock("DispatchEngine._lock")

    def tier_for(self, batch_size: int) -> str:
        """The tier (``small``/``medium``/``large``) a batch routes to."""
        if batch_size <= self.small_max:
            return "small"
        if batch_size < self.large_min:
            return "medium"
        return "large"

    def backend_for(self, batch_size: int) -> str:
        """The tier backend a batch of ``batch_size`` queries dispatches to."""
        return self.tiers[self.tier_for(batch_size)]

    def engine_for(self, batch_size: int) -> LookupEngine:
        name = self.backend_for(batch_size)
        eng = self._engines.get(name)
        if eng is None:
            with self._lock:           # don't jit the same tier twice
                eng = self._engines.get(name)
                if eng is None:
                    opts = dict(self._engine_opts.get(name, {}))
                    if self.monitor is not None \
                            and issubclass(_BACKENDS[name], _DeviceEngine):
                        opts["monitor"] = self.monitor
                    eng = make_engine(self.table, name, **opts)
                    self._engines[name] = eng
        return eng

    @hot_path
    def lookup(self, queries) -> np.ndarray:
        n = int(np.size(queries))
        eng = self.engine_for(n)
        mon = self.monitor
        if mon is None:
            return eng.lookup(queries)
        with mon.span(CH_TIER_PREFIX + self.tier_for(n), n, wall=True):
            return eng.lookup(queries)

    @hot_path
    def search(self, queries, side: str = "left") -> np.ndarray:
        """The query plane's primitive, routed by batch size exactly like
        ``lookup`` (every tier returns identical insertion ranks for exact-f32
        workloads, so dispatch stays semantics-preserving)."""
        n = int(np.size(queries))
        eng = self.engine_for(n)
        mon = self.monitor
        if mon is None:
            return eng.search(queries, side)
        with mon.span(CH_TIER_PREFIX + self.tier_for(n), n, wall=True):
            return eng.search(queries, side)

    def prewarm(self, batch_sizes=None) -> None:
        """Opt-in eager tier construction + compilation.

        Tier engines are normally built lazily on first use, which makes the
        first large batch after a snapshot swap eat the Pallas/XLA
        plan-and-compile latency as a p99 spike.  ``prewarm`` pays that cost
        up front: for each batch size (default: one representative size per
        tier) the owning tier engine is built and its lookup/search paths
        compiled at exactly that shape.  Called by the async pipeline on
        start with its flush-bucket sizes."""
        if batch_sizes is None:
            batch_sizes = [self.large_min]
            if self.small_max >= 1:
                batch_sizes.append(self.small_max)
            if self.small_max + 1 < self.large_min:
                batch_sizes.append(self.small_max + 1)
        for size in batch_sizes:
            eng = self.engine_for(int(size))
            warm = getattr(eng, "prewarm", None)
            if warm is not None:
                warm(batch_sizes=(int(size),))
