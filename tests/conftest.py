"""Shared test configuration.

The whole suite runs with the runtime concurrency sanitizer on by default
(``repro.analysis.sanitizer``: frozen published arrays, shard-set pin
tracking, lock-order watchdog).  Export ``REPRO_SANITIZE=0`` to measure or
debug without it; CI's bench jobs do exactly that.
"""
from __future__ import annotations

import os

os.environ.setdefault("REPRO_SANITIZE", "1")
