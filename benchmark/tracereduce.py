"""Reduction from a profiler trace to the per-layer numbers.

A trace is first normalised to plain data (``normalise``), which is
also the form of the small recorded trace the tests check:

    {"devices": {"<plane>": [[name, start_ns, dur_ns, meta], ...]},
     "host": [[name, start_ns, dur_ns], ...]}

``devices`` holds the op events of each device plane (nested events
included, as the profiler writes them); ``meta`` is a short string of
the event's stats, kept only for events whose name a reader asks for.
``host`` holds the benchmark's own spans (``load.push``,
``load.drain``), on the same clock.

Device busy time is the UNION of op intervals: nested events (a while
loop and the ops inside it) are counted once, never summed.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

HOST_SPANS = ("load.push", "load.drain")
# device lines that carry op events, in order of preference
OP_LINES = ("XLA Ops",)
KEEP_META = ("bv_first_set",)


def _stats_str(ev) -> str:
    try:
        return ";".join(f"{k}={v}" for k, v in ev.stats)[:2000]
    except Exception:  # noqa: BLE001 — stats are optional
        return ""


def op_name(name: str) -> str:
    """``%while.3 = (s32[] ...) while(...)`` -> ``while.3``: TPU op events
    carry the whole HLO instruction as their name."""
    return name.split(" = ", 1)[0].lstrip("%")


def normalise(pd, keep_meta: Sequence[str] = KEEP_META) -> Dict:
    """ProfileData -> the plain form above."""
    devices, host = {}, []
    for plane in pd.planes:
        pname = plane.name
        if pname.startswith("/device:") and "CPU" not in pname:
            lines = {ln.name: ln for ln in plane.lines}
            use = [lines[n] for n in OP_LINES if n in lines]
            evs = []
            for ln in use:
                for e in ln.events:
                    name = op_name(e.name)
                    meta = ""
                    if any(s in e.name for s in keep_meta):
                        meta = (e.name[:500] + ";" + _stats_str(e))
                    evs.append([name, float(e.start_ns),
                                float(e.duration_ns), meta])
            devices[pname] = evs
        elif pname.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name in HOST_SPANS:
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns)])
    return {"devices": devices, "host": host}


def load_dir(logdir: str) -> Optional[Dict]:
    """Normalise the newest ``.xplane.pb`` under a profiler log dir."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return None
    return normalise(ProfileData.from_file(files[-1]))


def union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    """Merge [start, end) intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def extent(tr: Dict) -> Tuple[float, float]:
    """The traced slice [lo, hi) in ns: the span of every event kept."""
    pts = [(e[1], e[1] + e[2]) for evs in tr["devices"].values()
           for e in evs] + [(h[1], h[1] + h[2]) for h in tr["host"]]
    if not pts:
        return 0.0, 0.0
    return min(p[0] for p in pts), max(p[1] for p in pts)


def busy_ns(evs: Sequence, lo: float, hi: float) -> float:
    """Union of op intervals of one device, clipped to [lo, hi)."""
    iv = [(max(lo, e[1]), min(hi, e[1] + e[2])) for e in evs]
    return sum(e - s for s, e in union(x for x in iv if x[1] > x[0]))


def device_busy(tr: Dict) -> Optional[Dict]:
    """{"busy_s": mean busy seconds over devices, "window_s": slice
    seconds, "idle_pct": mean idle share} or None without device ops."""
    devs = {k: v for k, v in tr["devices"].items() if v}
    if not devs:
        return None
    lo, hi = extent(tr)
    if hi <= lo:
        return None
    busy = [busy_ns(evs, lo, hi) for evs in devs.values()]
    b = sum(busy) / len(busy)
    return {"busy_s": b * 1e-9, "window_s": (hi - lo) * 1e-9,
            "idle_pct": 100.0 * (1.0 - b / (hi - lo)),
            "devices": len(devs)}


def named_events(tr: Dict, substr: str) -> List[list]:
    """Every device event whose name contains ``substr``."""
    return [e for evs in tr["devices"].values() for e in evs
            if substr in e[0]]


def top_level(evs: Sequence) -> List[list]:
    """Events not nested inside an earlier event of the same device."""
    out, end = [], float("-inf")
    for e in sorted(evs, key=lambda e: (e[1], -e[2])):
        if e[1] >= end:
            out.append(e)
            end = e[1] + e[2]
    return out


def top_ops(tr: Dict, n: int = 10) -> List[list]:
    """[name, seconds] of the top-level device ops that took most time,
    summed by name and averaged over devices."""
    tot: Dict[str, float] = {}
    devs = [v for v in tr["devices"].values() if v]
    for evs in devs:
        for e in top_level(evs):
            tot[e[0]] = tot.get(e[0], 0.0) + e[2]
    k = max(1, len(devs))
    rows = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns * 1e-9 / k] for name, ns in rows]


def idle_gaps(tr: Dict, n: int = 10) -> List[list]:
    """[what the host was doing, seconds] of the longest device-idle gaps
    (first device), each named by the host span that covers most of it,
    else ``unattributed``."""
    devs = [v for v in tr["devices"].values() if v]
    if not devs:
        return []
    lo, hi = extent(tr)
    busy = union((e[1], e[1] + e[2]) for e in devs[0])
    gaps, cur = [], lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:n]:
        cover: Dict[str, float] = {}
        for h in tr["host"]:
            ov = min(e, h[1] + h[2]) - max(s, h[1])
            if ov > 0:
                cover[h[0]] = cover.get(h[0], 0.0) + ov
        name = max(cover, key=cover.get) if cover else "unattributed"
        out.append([name, (e - s) * 1e-9])
    return out
