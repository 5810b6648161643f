"""The local tables' classify kernel in a profiler trace, for the reader
``local_classify_us.podsyn``.

The program runs the local tables' word-AND + priority encode in its own
Pallas kernel, ``acl_local_bv_first_set`` (vpp_tpu/ops/acl_bv.py); a TPU
op event is named by its HLO instruction (``acl_local_bv_first_set.2``).
A program without the kernel reads None, never an error.
"""

from __future__ import annotations

from typing import Dict, List, Optional

KERNEL = "acl_local_bv_first_set"


def events(trace: Optional[Dict]) -> List[list]:
    """The kernel's op events (``[name, start_ns, dur_ns, meta]``)."""
    if not trace:
        return []
    from benchmark.tracereduce import named_events

    return [e for e in named_events(trace, KERNEL)
            if e[0].split(".")[0] == KERNEL]


def mean_us(run: Dict) -> Optional[float]:
    """Mean device time of one kernel call, in us."""
    evs = events(run.get("trace"))
    if not evs:
        return None
    return sum(e[2] for e in evs) / len(evs) / 1e3
