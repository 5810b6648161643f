"""Fused step: share of packets that found no stored session, so that their
batch cannot take the classify-free fast tier."""


def read(run):
    from benchmark.pumpstats import fullpath_pkt_share

    return fullpath_pkt_share(run)
