"""Window deltas of the pump's stage counters that split the dispatch
call and the waits between stages (``DataplanePump.stats``: the
``vpp_tpu.trace.timed`` intervals and the queue counters).

A program that lacks a counter (an older commit) reads ``None``, never
an error; so does a window whose denominator is 0.
"""

from __future__ import annotations

from typing import Dict, Optional


def delta(run: Dict, key: str) -> Optional[float]:
    """stats1[key] - stats0[key], or None where either lacks the key."""
    a = run["stats0"].get(key)
    b = run["stats1"].get(key)
    if a is None or b is None:
        return None
    return b - a


def ratio(run: Dict, key: str, per: str,
          scale: float = 1.0) -> Optional[float]:
    """Window delta of ``key`` per window delta of ``per``, times
    ``scale``; None without the counters or with nothing in ``per``."""
    num, den = delta(run, key), delta(run, per)
    if num is None or not den or den <= 0:
        return None
    return num / den * scale


def ms_per_batch(run: Dict, key: str) -> Optional[float]:
    """Seconds of ``key`` per dispatch, in ms."""
    return ratio(run, key, "batches", 1e3)


def dispatch_cpu_pct(run: Dict) -> Optional[float]:
    """The dispatch thread's CPU time over its dispatch calls, as a share
    of their wall time. Low: the call waits (GIL, lock, a blocking
    transfer) instead of working."""
    return ratio(run, "t_dispatch_cpu", "t_dispatch", 100.0)
