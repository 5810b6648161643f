"""Fused step, pod-to-pod cell: share of packets that found no stored
session, as in ``fullpath_pkt_share.sat``."""


def read(run):
    from benchmark.pumpstats import fullpath_pkt_share

    return fullpath_pkt_share(run)
