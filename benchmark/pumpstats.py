"""Window deltas of the pump's own counters (``DataplanePump.stats``),
shared by the metric readers of the pump and fused-step layers."""

from __future__ import annotations

from typing import Dict, Optional

HOST_STAGES = ("t_pack", "t_dispatch", "t_fetch", "t_write")


def delta(run: Dict, key: str) -> float:
    return run["stats1"][key] - run["stats0"][key]


def host_us_per_pkt(run: Dict) -> Optional[float]:
    """Serial host time of the pump's stages per packet it wrote."""
    pkts = delta(run, "pkts")
    if pkts <= 0:
        return None
    return sum(delta(run, k) for k in HOST_STAGES) / pkts * 1e6


def pkts_per_dispatch(run: Dict) -> Optional[float]:
    """Packets per device dispatch (a chained fold counts as one)."""
    batches = delta(run, "batches")
    if batches <= 0:
        return None
    return delta(run, "pkts") / batches


def fullpath_pkt_share(run: Dict) -> Optional[float]:
    """Share of packets that found no stored session, so that their
    batch cannot take the classify-free fast tier, in percent."""
    pkts = delta(run, "pkts")
    if pkts <= 0:
        return None
    return 100.0 * (1.0 - delta(run, "fastpath_hits") / pkts)
