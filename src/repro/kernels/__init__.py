"""Pallas TPU kernels: compiled by Mosaic on TPU, interpreted on the CPU backend
(``platform.pallas_call``), where the tests validate them.

fitting_lookup -- the paper's hot path: batched learned-index probes
flash_attention -- blocked online-softmax attention (serving path)
rglru_scan -- blocked linear recurrence (RecurrentGemma serving path)
Each has a jit wrapper (ops.py) and a pure-jnp oracle (ref.py).
"""
from .ops import fitting_lookup, make_lookup_fn, make_plan
from .flash_attention import flash_attention
from .rglru_scan import rglru_scan_pallas

__all__ = ["fitting_lookup", "make_lookup_fn", "make_plan",
           "flash_attention", "rglru_scan_pallas"]
