"""Host stage timer of the served path: one interval, two readers.

``Timed`` wraps one host stage (the pump's pack, dispatch call, fetch,
tx write; the dataplane's upload and step call). On enter it opens a
profiler span (``jax.profiler.TraceAnnotation``); on exit it adds the
``time.perf_counter`` interval to ``stats[key]``. The span lands on the
host plane of the profiler's trace, on the same clock as the device
ops, and costs well under a microsecond when no profiler session is
running; the counter is always kept. So a traced run names each
device-idle gap by the stage the host was in, and an untraced run
reads the same intervals as cumulative seconds.

A class and not a generator-based context manager: it runs a few times
per dispatch on the hot path.
"""

from __future__ import annotations

import time
from typing import Optional

from jax.profiler import TraceAnnotation


class Timed:
    """``with Timed(name, stats, key):`` times the block as span
    ``name`` and adds its seconds to ``stats[key]``.

    ``lock``: held while adding, where several threads add to ``key``.
    ``cpu_key``: also add the thread's CPU time over the same interval
    (``time.thread_time``) to ``stats[cpu_key]``; wall minus CPU is
    time the thread did not run. ``key`` None keeps the span alone.
    A block that raises adds nothing (the stage did not complete), and
    ``cancel()`` drops the interval of a block that gives up. ``t0`` /
    ``t1`` hold the interval's ends for the caller."""

    __slots__ = ("_ann", "_stats", "_key", "_lock", "_cpu_key", "_c0",
                 "t0", "t1")

    def __init__(self, name: str, stats: Optional[dict] = None,
                 key: Optional[str] = None, lock=None,
                 cpu_key: Optional[str] = None):
        self._ann = TraceAnnotation(name)
        self._stats = stats
        self._key = key
        self._lock = lock
        self._cpu_key = cpu_key
        self._c0 = 0.0
        self.t0 = self.t1 = 0.0

    def __enter__(self) -> "Timed":
        self._ann.__enter__()
        if self._cpu_key is not None:
            self._c0 = time.thread_time()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.t1 = time.perf_counter()
        cpu = (time.thread_time() - self._c0
               if self._cpu_key is not None else 0.0)
        self._ann.__exit__(exc_type, exc, tb)
        if exc_type is None and self._key is not None:
            if self._lock is None:
                self._add(cpu)
            else:
                with self._lock:
                    self._add(cpu)
        return False

    def _add(self, cpu: float) -> None:
        self._stats[self._key] += self.t1 - self.t0
        if self._cpu_key is not None:
            self._stats[self._cpu_key] += cpu

    def cancel(self) -> None:
        """Keep the span, add nothing to the counters on exit."""
        self._key = None
