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
Every backend returns ``np.searchsorted``'s ranks on the float64 key column
for any float64 query.  The ``numpy`` backend compares in float64; the device
backends choose their key form from the data (:func:`device_index`): float32
when every key is a float32 (the host then rounds each query toward the side
whose count it keeps, and answers a lookup of a value float32 cannot hold
with -1), else the keys' order-preserving 64-bit codes as two int32 words
(``repro.index.keycode``), searched in a window widened by a slack measured
on the table and checked at its edges.  ``DeviceIndex`` is the device form of
a ``SegmentTable`` (re-exported by repro.core.jax_index for compatibility).
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Literal, NamedTuple, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.contracts import hot_path
from repro.analysis.sanitizer import make_lock

from . import keycode
from .keycode import Wide
from .query import QueryVerbs
from .table import SegmentTable, numpy_lookup, numpy_search
from .telemetry import (CH_D2H, CH_ENCODE, CH_H2D, CH_LAUNCH, CH_TIER_PREFIX,
                        CH_WIDEN, NO_SPAN)


def _bucket_size(n: int) -> int:
    """The power-of-two batch bucket ``n`` pads into (floor 16, so tiny
    batches share a handful of shapes instead of one each)."""
    return max(16, 1 << (int(n) - 1).bit_length())


class DeviceIndex(NamedTuple):
    """Device form of a SegmentTable.  ``keys`` is the float32 column when
    every key is a float32 and the float32 predictor cannot overflow
    (:func:`_narrow_fits`), else the wide form: each key's order-preserving
    64-bit code as two int32 words (:class:`~repro.index.keycode.Wide`,
    8 B a key), searched in a window
    widened by ``slack`` and checked at its edges.  Both forms route and
    predict with the float32 segment arrays."""
    seg_start: jax.Array  # (S,) f32  first key of each segment
    slope: jax.Array      # (S,) f32
    base: jax.Array       # (S,) i32  global position of segment start
    seg_end: jax.Array    # (S,) i32  one past the segment end
    keys: jax.Array | Wide  # (N,) f32, or (N,) x 2 i32 words: sorted keys
    error: int            # static
    slack: int = 0        # static: the wide form's reach beyond ``error``


def slack_cap(error: int) -> int:
    """The largest slack whose window still fits the float32 form's kernel
    block (``make_plan``): 63 at error 256.  The wide form's geometry is
    then the float32 form's; a query its predictor misses by more is
    settled by ``fit.widen``."""
    window = 2 * error + 2
    return (max(128, _round_up(window, 128)) - window) // 2


def _narrow_fits(table: SegmentTable) -> bool:
    """Whether the float32 form answers this table exactly: every key is a
    float32, and the float32 predictor's position stays far inside int32
    for every query from the first key to just past the last (the host
    moves queries beyond them there, :func:`_encode_narrow`).  A gap
    between segments worth 2^30 ranks at its segment's slope fails it."""
    if not keycode.f32_exact(table.keys):
        return False
    if table.n_keys == 0:
        return True
    start = table.start_key.astype(np.float32)
    end = np.append(start[1:], np.nextafter(np.float32(table.keys[-1]),
                                            np.float32(np.inf)))
    with np.errstate(over="ignore", invalid="ignore"):
        reach = (end - start) * table.slope.astype(np.float32)
    return bool(np.all(table.base + reach < 2 ** 30))   # NaN, inf fail


def key_slack(table: SegmentTable) -> int | None:
    """How far the wide form's window reaches past ``error`` for this
    table, or None when the float32 form serves it (:func:`_narrow_fits`).

    The device predicts in float32, and keys that share one float32 value
    share one prediction, so it can miss a key's rank by more than
    ``error``.  This evaluates that predictor on the table's own keys and
    takes its largest miss beyond ``error``, plus 1 for an absent query
    (the predictor is monotone inside a segment, so an absent query's rank
    is at most one past its neighbours'), capped at :func:`slack_cap`.
    Exactness does not rest on it: the search checks every answer at its
    window's edges."""
    if _narrow_fits(table):
        return None
    n = table.n_keys
    k = table.keys.astype(np.float32)
    start = table.start_key.astype(np.float32)
    sid = np.clip(np.searchsorted(start, k, side="right") - 1, 0,
                  start.size - 1)
    local = (k - start[sid]) * table.slope.astype(np.float32)[sid]
    base = table.base[sid]
    pred = base + np.round(np.clip(local, -2 ** 31, 2 ** 31 - 1)
                           ).astype(np.int64)
    pred = np.minimum(np.maximum(pred, base), table.seg_end[sid])
    miss = int(np.abs(pred - np.arange(n)).max()) if n else 0
    return min(max(0, miss - table.error) + 1, slack_cap(table.error))


def device_index(table: SegmentTable) -> DeviceIndex:
    """Convert (and cache on the table -- snapshots are shared by engines).
    The key form and the wide form's slack follow the data
    (:func:`key_slack`)."""
    dev = getattr(table, "_device_cache", None)
    if dev is None:
        # cast on the host: a device-side cast compiles once per shape
        def put(a, dtype):
            return jax.device_put(np.asarray(a, dtype))

        slack = key_slack(table)
        if slack is None:
            keys = put(table.keys, np.float32)
        else:
            code = keycode.encode(table.keys)
            keys = Wide(put(code.hi, np.int32), put(code.lo, np.int32))
        dev = DeviceIndex(
            seg_start=put(table.start_key, np.float32),
            slope=put(table.slope, np.float32),
            base=put(table.base, np.int32),
            seg_end=put(table.seg_end, np.int32),
            keys=keys,
            error=int(table.error),
            slack=slack or 0,
        )
        object.__setattr__(table, "_device_cache", dev)  # frozen dataclass
    return dev


class WideProbe(NamedTuple):
    """Queries of the wide form, as the device reads them."""
    route: jax.Array   # (nq,) f32  the query rounded: routes and predicts
    key: Wide          # (nq,) x 2 i32  its code: compared with the keys
    weight: jax.Array  # (nq,) i32  1 for a query, 0 for bucket padding

    @classmethod
    def unpack(cls, rows: jax.Array) -> "WideProbe":
        """From the (4, nq) int32 block :func:`_encode_wide` sends."""
        return cls(jax.lax.bitcast_convert_type(rows[0], jnp.float32),
                   Wide(rows[1], rows[2]), rows[3])


def _is_wide(idx: DeviceIndex) -> bool:
    return isinstance(idx.keys, Wide)


def _parts(queries) -> tuple[jax.Array, jax.Array | Wide]:
    """``(route, key)``: what a query routes with, what it compares with."""
    if isinstance(queries, WideProbe):
        return queries.route, queries.key
    return queries, queries


def _take(keys, i):
    """``keys[i]``, of either key form."""
    return jax.tree.map(lambda col: col[i], keys)


def _bisect_step(keys, queries, side: str):
    """One step of a bisect of ``[lo, hi)``: ``lo`` moves past ``mid`` when
    its key is below the query (``keycode.below(side)``)."""
    n = keys.shape[0]
    below = keycode.below(side)

    def body(_, lh):
        lo, hi = lh
        mid = (lo + hi) // 2
        go = below(_take(keys, jnp.minimum(mid, n - 1)), queries) & (lo < hi)
        return jnp.where(go, mid + 1, lo), jnp.where(go, hi, mid)

    return body


def _full_search(keys, queries, side: str) -> jax.Array:
    """``searchsorted(keys, queries, side)`` over the whole column."""
    if not isinstance(keys, Wide):
        return jnp.searchsorted(keys, queries, side=side)
    n = keys.shape[0]
    lo = jnp.zeros(queries.shape, jnp.int32)
    hi = jnp.full(queries.shape, n, jnp.int32)
    lo, _ = jax.lax.fori_loop(0, n.bit_length(),
                              _bisect_step(keys, queries, side), (lo, hi))
    return lo


# --------------------------------------------------------------------- device
def snap_leftmost(keys, queries, rank: jax.Array, hit: jax.Array
                  ) -> jax.Array:
    """Snap duplicate hits to the leftmost occurrence (device mirror of the
    ``numpy_lookup`` fix): when a found rank's left neighbour still equals
    the query, the duplicate run straddles a segment boundary and the
    window search returned an in-segment rank.  ``lax.cond`` skips the
    full-column bisect entirely unless some query actually needs it, so the
    duplicate-free fast path pays one extra gather."""
    need = hit & (rank > 0) & keycode.eq(
        _take(keys, jnp.maximum(rank - 1, 0)), queries)
    fixed = jax.lax.cond(
        jnp.any(need),
        lambda: _full_search(keys, queries, "left").astype(rank.dtype),
        lambda: rank)
    return jnp.where(need, fixed, rank)


def snap_side(keys, queries, rank: jax.Array, side: str) -> jax.Array:
    """Side-generalized duplicate snap for insertion-rank searches (the
    ``search`` primitive): a bounded window parks inside a duplicate run that
    extends past it, which is detectable from the landing position alone --
    for ``side="left"`` the left neighbour still equals the query, for
    ``side="right"`` the landing key itself does.  ``lax.cond`` skips the
    full-column searchsorted unless some query actually needs it (the same
    fast-path discipline as :func:`snap_leftmost`)."""
    n = keys.shape[0]
    if side == "left":
        need = (rank > 0) & keycode.eq(
            _take(keys, jnp.maximum(rank - 1, 0)), queries)
    else:
        need = (rank < n) & keycode.eq(
            _take(keys, jnp.minimum(rank, n - 1)), queries)
    fixed = jax.lax.cond(
        jnp.any(need),
        lambda: _full_search(keys, queries, side).astype(rank.dtype),
        lambda: rank)
    return jnp.where(need, fixed, rank)


def _widen(idx: DeviceIndex, queries: WideProbe, rank: jax.Array,
           lo: jax.Array, hi: jax.Array, side: str
           ) -> tuple[jax.Array, jax.Array]:
    """Settle a wide-form search of the windows ``[lo, hi)``.  ``rank`` is
    the column's rank exactly when the keys just outside the window lie on
    the right sides of the query: below it before ``lo``, not below it from
    ``hi`` on.  Every other query -- one the float32 predictor missed by
    more than the slack, a duplicate run past an edge -- is searched again
    over the whole column, under ``lax.cond``, so a batch that needs none
    pays two gathers.  Returns the ranks and how many queries (not padding)
    were searched again.  The ``fit.widen`` scope."""
    keys = idx.keys
    n = keys.shape[0]
    below = keycode.below(side)
    q = queries.key
    with jax.named_scope("fit.widen"):
        before = _take(keys, jnp.clip(lo - 1, 0, n - 1))
        after = _take(keys, jnp.clip(hi, 0, n - 1))
        need = ~(((lo <= 0) | below(before, q))
                 & ((hi >= n) | ~below(after, q)))
        fixed = jax.lax.cond(jnp.any(need),
                             lambda: _full_search(keys, q, side),
                             lambda: rank)
        return (jnp.where(need, fixed, rank),
                jnp.sum(jnp.where(need, queries.weight, 0)))


def _member(idx: DeviceIndex, queries: WideProbe, rank: jax.Array
            ) -> jax.Array:
    """A lookup's answer from the exact left insertion rank: the rank when
    the key there is the query, else -1."""
    n = idx.keys.shape[0]
    with jax.named_scope("fit.snap"):
        hit = (rank < n) & keycode.eq(
            _take(idx.keys, jnp.minimum(rank, n - 1)), queries.key)
        return jnp.where(hit, rank, -1)


def predict_positions(idx: DeviceIndex, queries: jax.Array) -> jax.Array:
    """Interpolated (approximate) global positions; error <= idx.error by Eq. 1.

    Device mirror of SegmentTable.predict: route, FMA, clamp into the owning
    segment's position range so inter-segment gap queries cannot overshoot."""
    sid = jnp.clip(jnp.searchsorted(idx.seg_start, queries, side="right") - 1,
                   0, idx.seg_start.shape[0] - 1)
    local = (queries - idx.seg_start[sid]) * idx.slope[sid]
    pred = idx.base[sid] + jnp.round(local).astype(jnp.int32)
    return jnp.clip(pred, idx.base[sid], idx.seg_end[sid])


def _bisect(idx: DeviceIndex, queries, pred: jax.Array, side: str
            ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Bisect each query's window ``[lo, hi)``, ``error + slack`` keys each
    side of its predicted position.  Returns ``(rank, lo, hi)``: ``rank`` is
    ``lo`` plus the window's keys below the query (``keycode.below``).  The
    ``fit.bisect`` scope."""
    keys = idx.keys
    n = keys.shape[0]
    e = idx.error + idx.slack
    steps = int(np.ceil(np.log2(2 * e + 2)))
    with jax.named_scope("fit.bisect"):
        lo = jnp.clip(pred - e, 0, n).astype(jnp.int32)
        hi = jnp.clip(pred + e + 1, 0, n).astype(jnp.int32)
        rank, _ = jax.lax.fori_loop(0, steps, _bisect_step(keys, queries, side),
                                    (lo, hi))
    return rank, lo, hi


def xla_lookup(idx: DeviceIndex, queries, strategy: Literal["window", "bisect"]
               = "window"):
    """Batched point lookup, rank or -1.  jit-safe; ``error`` is static.
    Its device steps carry the ``fit.prelude``, ``fit.bisect`` and
    ``fit.snap`` named scopes.  On the wide form (a :class:`WideProbe` of
    queries) it is the left :func:`xla_search` and a membership check, and
    returns ``(answers, widened)``."""
    if _is_wide(idx):
        rank, widened = xla_search(idx, queries, "left", strategy)
        return _member(idx, queries, rank), widened
    n = idx.keys.shape[0]
    with jax.named_scope("fit.prelude"):
        pred = predict_positions(idx, queries)
    e = idx.error
    if strategy == "window":
        w = 2 * e + 2
        start = jnp.clip(pred - e, 0, jnp.maximum(n - w, 0)).astype(jnp.int32)
        offs = start[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]
        vals = _take(idx.keys, jnp.minimum(offs, n - 1))
        lt = keycode.lt(vals, queries[:, None]).sum(axis=1).astype(jnp.int32)
        rank = start + lt
        hit = keycode.eq(vals, queries[:, None]).any(axis=1)
        with jax.named_scope("fit.snap"):
            rank = snap_leftmost(idx.keys, queries, rank, hit)
            return jnp.where(hit, rank, -1)
    lo, _, _ = _bisect(idx, queries, pred, "left")
    with jax.named_scope("fit.bisect"):
        ok = (lo < n) & keycode.eq(_take(idx.keys, jnp.minimum(lo, n - 1)),
                                   queries)
    with jax.named_scope("fit.snap"):
        lo = snap_leftmost(idx.keys, queries, lo, ok)
        return jnp.where(ok, lo, -1)


def xla_search(idx: DeviceIndex, queries, side: str = "left",
               strategy: Literal["window", "bisect"] = "bisect"):
    """Batched bounded-window rank search: the device mirror of
    :func:`repro.index.table.numpy_search`.  Returns the insertion rank of
    every query -- ``searchsorted(keys, q, side)`` -- via the interpolated
    +-error window; jit-safe, ``error``/``side``/``strategy`` static.

    ``window`` counts the in-window keys strictly below (``side="left"``) or
    at-or-below (``side="right"``) each query; ``bisect`` runs log2(2e+2)
    halving steps with the side's comparison.  The float32 form ends with
    :func:`snap_side`, so duplicate runs extending past the window still
    resolve to the exact global rank; the wide form's window reaches
    ``error + slack`` and ends with :func:`_widen`, returning ``(ranks,
    widened)``."""
    if side not in ("left", "right"):
        raise ValueError(f"side must be 'left' or 'right', got {side!r}")
    keys = idx.keys
    n = keys.shape[0]
    route, q = _parts(queries)
    with jax.named_scope("fit.prelude"):
        pred = predict_positions(idx, route)
    if strategy == "window":
        e = idx.error + idx.slack
        w = 2 * e + 2
        start = jnp.clip(pred - e, 0, jnp.maximum(n - w, 0)).astype(jnp.int32)
        offs = start[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]
        valid = offs < n                       # clamped gathers replicate the
        vals = _take(keys, jnp.minimum(offs, n - 1))  # last key: mask them
        cmp = keycode.below(side)(vals, jax.tree.map(lambda c: c[:, None], q))
        rank = start + (valid & cmp).sum(axis=1).astype(jnp.int32)
        if _is_wide(idx):
            return _widen(idx, queries, rank, start, start + w, side)
        with jax.named_scope("fit.snap"):
            return snap_side(keys, q, rank, side)
    rank, lo, hi = _bisect(idx, q, pred, side)
    if _is_wide(idx):
        return _widen(idx, queries, rank, lo, hi, side)
    with jax.named_scope("fit.snap"):
        return snap_side(keys, q, rank, side)


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
    """Kernel geometry for a window of ``error`` keys each side (the wide
    form passes ``error + slack``)."""
    # lazy: repro.kernels imports this module for its thin wrappers
    from repro.kernels.fitting_lookup import ROWS

    window = 2 * error + 2
    kb = max(128, _round_up(window, 128))
    # whole kernel grid steps: ROWS key blocks each
    n_pad = _round_up(max(n_keys, kb), ROWS * kb)
    return LookupPlan(kb=kb, window=window, n_blocks=n_pad // kb, n_pad=n_pad)


def pad_keys(keys, plan: LookupPlan):
    """The key column padded to ``plan.n_pad`` with keys above every key:
    +inf, or ``keycode.TOP`` words for the wide form."""
    pad = plan.n_pad - keys.shape[0]
    if isinstance(keys, Wide):
        return jax.tree.map(
            lambda w: jnp.pad(w, (0, pad), constant_values=keycode.TOP), keys)
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


def _pallas_bucketize(idx: DeviceIndex, queries, plan: LookupPlan,
                      qcap: int) -> tuple:
    """The XLA prelude shared by :func:`pallas_lookup` and
    :func:`pallas_search`: router + interpolation -> window starts -> queries
    bucketed by the key block their window starts in.  Returns ``(q_b,
    qlo_b, qlo, slots)``: per-block query keys (filler above every key),
    per-block and per-query global window starts, and each query's
    :class:`BucketSlots` coordinates, which the caller reads its answers
    back at.  A query not ``ok`` overflowed its bucket and must be answered
    by the caller's fallback."""
    route, q = _parts(queries)
    nq = route.shape[0]
    pred = predict_positions(idx, route)
    qlo = jnp.clip(pred - (idx.error + idx.slack), 0,
                   plan.n_pad - plan.window).astype(jnp.int32)
    blk = qlo // plan.kb                                    # owning key block
    order = jnp.argsort(blk, stable=True).astype(jnp.int32)
    blk_s = blk[order]
    slot = jnp.arange(nq, dtype=jnp.int32) - jnp.searchsorted(
        blk_s, blk_s, side="left").astype(jnp.int32)        # rank within bucket
    ok = slot < qcap
    slot = jnp.where(ok, slot, qcap)     # overflow: off the row, so dropped
    shape = (plan.n_blocks, qcap)
    q_b = jax.tree.map(
        lambda w: (jnp.full(shape, jnp.inf, jnp.float32)
                   if w.dtype == jnp.float32
                   else jnp.full(shape, keycode.TOP, jnp.int32)), q)
    qlo_b = jnp.zeros(shape, jnp.int32)
    q_b = jax.tree.map(
        lambda b, w: b.at[blk_s, slot].set(w[order], mode="drop"), q_b, q)
    qlo_b = qlo_b.at[blk_s, slot].set(qlo[order], mode="drop")
    return q_b, qlo_b, qlo, BucketSlots(order, blk_s, slot, ok)


def _read_back(slots: BucketSlots, ans: jax.Array) -> jax.Array:
    """``ans`` (one answer per query in block order, gathered at its
    clipped slot) back in query order, ``_UNSET`` where the query
    overflowed: one unique-index scatter of nq."""
    ans = jnp.where(slots.ok, ans, _UNSET)
    return jnp.zeros_like(ans).at[slots.order].set(ans, unique_indices=True)


def pallas_lookup(idx: DeviceIndex, queries, *, qcap: int = 256,
                  fallback: bool = True):
    """Batched point lookup via the Pallas kernel.  Returns ranks (-1 absent).

    XLA prelude (router + interpolation + bucketing) -> Pallas compare-reduce
    kernel -> each query's rank and hit read at its own bucket slot (nq
    gathers, put back in query order) + bisect fallback for bucket overflow.
    ``idx.error`` must be a Python int (it sizes the kernel window): jit this
    with the index arrays as arguments and ``error`` static, as the engines
    do.  The three steps carry the ``fit.prelude``, ``fit.kernel`` and
    ``fit.snap`` named scopes, the overflow fallback ``fit.bisect``.  On the
    wide form it is the left :func:`pallas_search` (which always answers an
    overflowed query) and a membership check, and returns ``(answers,
    widened)``."""
    if _is_wide(idx):
        rank, widened = pallas_search(idx, queries, "left", qcap=qcap)
        return _member(idx, queries, rank), widened
    # lazy: repro.kernels imports this module for its thin wrappers
    from repro.kernels.fitting_lookup import fitting_lookup_pallas

    plan = make_plan(int(idx.keys.shape[0]), int(idx.error))
    queries = queries.astype(jnp.float32)
    with jax.named_scope("fit.prelude"):
        keys_padded = pad_keys(idx.keys, plan)
        q_b, qlo_b, _, slots = _pallas_bucketize(idx, queries, plan, qcap)

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


def pallas_search(idx: DeviceIndex, queries, side: str = "left", *,
                  qcap: int = 256):
    """Batched insertion-rank search via the Pallas compare-reduce kernel.

    Same XLA prelude (router + interpolation + bucketing), kernel geometry
    and read-back at each query's bucket slot as :func:`pallas_lookup`; the
    kernel's masked compare-reduce simply counts with the side's comparison
    (``<`` for left, ``<=`` for right) so ``rank = window_start + count`` is
    the searchsorted insertion rank.  Bucket-overflow queries fall back to
    the XLA bisect search.  The float32 form ends with :func:`snap_side`,
    which resolves duplicate runs extending past the window; the wide
    form's window reaches ``error + slack``, its overflowed queries take
    the bounded bisect, and it ends with :func:`_widen`, returning
    ``(ranks, widened)``."""
    if side not in ("left", "right"):
        raise ValueError(f"side must be 'left' or 'right', got {side!r}")
    # lazy: repro.kernels imports this module for its thin wrappers
    from repro.kernels.fitting_lookup import fitting_lookup_pallas

    wide = _is_wide(idx)
    plan = make_plan(int(idx.keys.shape[0]), int(idx.error + idx.slack))
    if not wide:
        queries = queries.astype(jnp.float32)
    with jax.named_scope("fit.prelude"):
        keys_padded = pad_keys(idx.keys, plan)
        q_b, qlo_b, qlo, slots = _pallas_bucketize(idx, queries, plan, qcap)

    with jax.named_scope("fit.kernel"):
        rank_b, _ = fitting_lookup_pallas(
            keys_padded, q_b, qlo_b, kb=plan.kb, window=plan.window,
            side=side)

    with jax.named_scope("fit.snap"):
        res = _read_back(slots, rank_b.at[slots.blk, slots.slot].get(
            mode="clip"))
    need = res == _UNSET                         # bucket-overflow queries
    if wide:
        route, q = _parts(queries)
        held = (res, qlo, qlo + plan.window)

        def bisect_overflow():
            got = _bisect(idx, q, predict_positions(idx, route), side)
            return tuple(jnp.where(need, a, b) for a, b in zip(got, held))

        with jax.named_scope("fit.bisect"):
            res, lo, hi = jax.lax.cond(jnp.any(need), bisect_overflow,
                                       lambda: held)
        return _widen(idx, queries, res, lo, hi, side)
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


@functools.partial(jax.jit,
                   static_argnames=("impl", "error", "opts", "slack"))
def _run_on_index(arrays, queries, *, impl, error, opts, slack=0):
    """The one jitted entry of every device engine: ``impl(DeviceIndex,
    queries, **opts)`` with the table's arrays as *arguments*, so an
    executable serves every table of the same shapes instead of carrying the
    key column as a constant.  ``error`` and ``slack`` size the window and
    ``opts`` (sorted ``(name, value)`` pairs: strategy, side, qcap) pick the
    code path, so all three are static.  On the wide form ``queries`` is
    :func:`_encode_wide`'s block, and the answers come back followed by the
    number of widened queries."""
    idx = DeviceIndex(*arrays, error, slack)
    if _is_wide(idx):
        out, widened = impl(idx, WideProbe.unpack(queries), **dict(opts))
        return jnp.concatenate([out, widened[None].astype(out.dtype)])
    return impl(idx, queries, **dict(opts))


def _encode_narrow(queries, side: str | None, span: tuple
                   ) -> tuple[np.ndarray, np.ndarray | None]:
    """Queries for the float32 form, where every key is a float32: each
    query rounded to float32, toward the side whose count it keeps when
    float32 cannot hold it (up for a left search: no key lies between it
    and its rounding; down for a right one), then moved into ``span`` --
    the float32 values just below the first key and just above the last,
    which answer as anything beyond them does, and which the float32
    predictor places without overflow.  Returns the float32 queries and a
    mask of those the host's float64 tier answers instead, or None: NaN
    and any query still infinite, and for a lookup (``side`` None) also
    every query float32 cannot hold, which matches no key."""
    x = np.asarray(queries)
    with np.errstate(over="ignore"):    # beyond float32's range: +-inf
        q = x.astype(np.float32)
    off = q != x
    if side is not None and off.any():
        if side == "left":
            step, toward = q < x, np.float32(np.inf)
        else:
            step, toward = q > x, np.float32(-np.inf)
        with np.errstate(over="ignore"):    # float32's max steps to inf
            q[step] = np.nextafter(q[step], toward)
    np.clip(q, *span, out=q)
    host = ~np.isfinite(q)
    if side is None:
        host |= off
    return q, (host if host.any() else None)


def _encode_wide(queries, size: int) -> np.ndarray:
    """Queries for the wide form, padded to ``size`` by repeating them: one
    (4, size) int32 block of the float32 rounding's bits (routing and
    prediction), the two words of each query's code (``keycode.encode``),
    and a weight of 1 for a query, 0 for padding (:class:`WideProbe`)."""
    x = np.asarray(queries, np.float64).ravel()
    padded = np.resize(x, size)
    code = keycode.encode(padded)
    weight = (np.arange(size) < x.size).astype(np.int32)
    with np.errstate(over="ignore"):    # beyond float32's range: +-inf
        route = padded.astype(np.float32)
    return np.stack([route.view(np.int32), code.hi, code.lo, weight])


class _DeviceEngine(QueryVerbs):
    """Shared scaffolding: convert the table once, run the backend's
    ``_lookup_impl`` / ``_search_impl`` through :func:`_run_on_index`.

    Each batch is padded to its power-of-two bucket (:func:`_bucket_size`;
    padding lanes repeat the batch, so they spread over the key blocks like
    the real queries) and the tail is sliced off: a sharded service splits
    every batch by routing, and without the bucket each distinct per-shard
    size would be a fresh compile.

    ``monitor`` (a ``repro.index.telemetry.Monitor``) splits each call into
    three spans: ``engine.h2d`` (the queries put in the key form and padded
    -- its child span ``engine.encode`` -- then the host-to-device copy),
    ``engine.launch`` (the jitted call until it returns) and ``engine.d2h``
    (the blocking read of the answer).  A call on the wide form also records
    its widened queries on ``engine.widen``."""

    _lookup_impl: Callable
    _search_impl: Callable

    def __init__(self, table: SegmentTable, lookup_opts: dict | None = None,
                 search_opts: dict | None = None, monitor=None):
        self.table = table
        self.index = device_index(table)
        self.monitor = monitor
        self._wide = _is_wide(self.index)
        if table.n_keys and not self._wide:   # see _encode_narrow
            ends = np.asarray(table.keys[[0, -1]], np.float32)
            with np.errstate(over="ignore"):
                self._span = tuple(np.nextafter(ends, np.float32([-np.inf,
                                                                  np.inf])))
        self._arrays = tuple(self.index)[:5]
        self._lookup_opts = tuple(sorted((lookup_opts or {}).items()))
        self._search_opts = dict(search_opts or {})

    def _run(self, impl, queries, opts, side: str | None) -> np.ndarray:
        """Answer ``queries`` with ``impl``: a lookup when ``side`` is None,
        else a search of that side."""
        mon = self.monitor
        with (NO_SPAN if mon is None else mon.span(CH_H2D)):
            with (NO_SPAN if mon is None else mon.span(CH_ENCODE)):
                shape = np.shape(queries)
                n = math.prod(shape)
                size = _bucket_size(n) if n else 0
                host = None
                if self._wide:
                    q = _encode_wide(queries, size)
                else:
                    q, host = _encode_narrow(queries, side, self._span)
                    q = q.ravel()
                    if n:
                        q = np.resize(q, size)
            dq = jnp.asarray(q)
        with (NO_SPAN if mon is None else mon.span(CH_LAUNCH)):
            out = _run_on_index(self._arrays, dq, impl=impl,
                                error=self.index.error,
                                slack=self.index.slack, opts=opts)
        with (NO_SPAN if mon is None else mon.span(CH_D2H)):
            out = np.asarray(out)
        if self._wide and mon is not None:
            mon.record(CH_WIDEN, int(out[-1]))
        out = out[:n].reshape(shape)
        if host is not None:           # the float64 column's own answers
            x = np.asarray(queries, np.float64)[host]
            out = out.copy()
            out[host] = (numpy_lookup(self.table, x) if side is None
                         else numpy_search(self.table, x, side))
        return out

    def lookup(self, queries) -> np.ndarray:
        if self.table.n_keys == 0:   # gathers on a 0-length device array are
            q = np.asarray(queries)  # undefined; an empty table always misses
            return np.full(q.shape, -1, np.int64)
        return self._run(type(self)._lookup_impl, queries, self._lookup_opts,
                         None)

    def search(self, queries, side: str = "left") -> np.ndarray:
        if side not in ("left", "right"):
            raise ValueError(f"side must be 'left' or 'right', got {side!r}")
        if self.table.n_keys == 0:   # empty table: every rank is 0
            return np.zeros(np.asarray(queries).shape, np.int64)
        opts = tuple(sorted({**self._search_opts, "side": side}.items()))
        out = self._run(type(self)._search_impl, queries, opts, side)
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
    together with the table.  Every tier returns ``np.searchsorted``'s ranks
    on the float64 key column for any float64 query (see the module
    docstring), so dispatch is semantics-preserving.

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
        ``lookup`` (every tier returns ``np.searchsorted``'s insertion ranks,
        so dispatch stays semantics-preserving)."""
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
