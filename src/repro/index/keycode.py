"""Order-preserving 64-bit key codes: how the device compares float64 keys.

The device tiers compare a key column in float32 when every key survives the
round trip float64 -> float32 -> float64 (:func:`f32_exact`).  A column that
does not -- the paper's Maps longitudes, whole-second timestamps past 2^24 --
is compared through a code instead:

    code(x) = bits(x) with every bit flipped     if x is negative
            = bits(x) with the sign bit flipped  otherwise

``code`` orders unsigned 64-bit integers as ``np.sort`` orders the floats,
once -0.0 is made +0.0 and every NaN one positive quiet NaN (so NaN sorts
last).  The device has no 64-bit integers, so a code travels as two int32
words (:class:`Wide`), each xor-ed with 0x80000000: signed compares of the
words, high word first, then give the unsigned order of the code.

The compares (:func:`lt`, :func:`le`, :func:`eq`) take either a float32
array or a :class:`Wide` pair and use only operators, so the same call
serves numpy, traced jax and Pallas kernel values.  numpy only: the host's
float64 tier in ``repro.index.table`` stays the reference.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import numpy as np

_SIGN = np.uint64(1 << 63)
_WORD = np.uint32(0x80000000)
_QUIET_NAN = np.uint64(0x7FF8000000000000)
TOP = np.iinfo(np.int32).max   # a word above every key's (padding, filler)


class Wide(NamedTuple):
    """A column of 64-bit key codes as two int32 words (a jax pytree)."""
    hi: Any   # high 32 bits of the code, xor 0x80000000
    lo: Any   # low 32 bits of the code, xor 0x80000000

    @property
    def shape(self) -> tuple[int, ...]:
        return self.hi.shape


def f32_exact(keys) -> bool:
    """Whether every key survives float64 -> float32 -> float64 (NaN does
    not), so float32 compares order and match the keys as float64 does."""
    k = np.asarray(keys, np.float64)
    return bool(np.array_equal(k.astype(np.float32), k))


def encode(values) -> Wide:
    """The two words of each value's order-preserving code."""
    x = np.asarray(values, np.float64)
    bits = np.where(np.isnan(x), _QUIET_NAN,
                    np.where(x == 0, np.uint64(0), x.view(np.uint64)))
    code = np.where(bits >= _SIGN, ~bits, bits | _SIGN)
    hi = (code >> np.uint64(32)).astype(np.uint32) ^ _WORD
    lo = code.astype(np.uint32) ^ _WORD
    return Wide(hi.view(np.int32), lo.view(np.int32))


def lt(a, b):
    """``a < b`` in key order."""
    if isinstance(a, Wide):
        return (a.hi < b.hi) | ((a.hi == b.hi) & (a.lo < b.lo))
    return a < b


def le(a, b):
    """``a <= b`` in key order."""
    if isinstance(a, Wide):
        return (a.hi < b.hi) | ((a.hi == b.hi) & (a.lo <= b.lo))
    return a <= b


def eq(a, b):
    """``a == b`` in key order."""
    if isinstance(a, Wide):
        return (a.hi == b.hi) & (a.lo == b.lo)
    return a == b


def below(side: str) -> Callable:
    """The compare a ``searchsorted`` of ``side`` counts keys with: ``lt``
    for "left" (keys before the first key >= q), ``le`` for "right"."""
    return lt if side == "left" else le
