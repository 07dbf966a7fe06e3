"""Pallas calls whose execution mode follows the platform they lower for.

A ``pallas_call`` is compiled by Mosaic when the program is lowered for a
TPU and run by the Pallas interpreter when it is lowered for the CPU.  The
choice is made by :func:`jax.lax.platform_dependent` at lowering time, so the
same jitted function compiles the real kernel for a TPU (attached, or a
described topology in an ahead-of-time compile) and stays runnable in CPU
tests; there is no ``interpret`` option to forget on the chip.
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl


def pallas_call(kernel, **kwargs):
    """``pl.pallas_call(kernel, **kwargs)``, interpreted only on the CPU."""
    compiled = pl.pallas_call(kernel, **kwargs)
    interpreted = pl.pallas_call(kernel, interpret=True, **kwargs)

    def call(*args):
        return jax.lax.platform_dependent(*args, cpu=interpreted,
                                          default=compiled)

    return call
