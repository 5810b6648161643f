"""Pump layer: packets per device dispatch over the window (a chained fold
of K buckets is one dispatch)."""


def read(run):
    from benchmark.pumpstats import pkts_per_dispatch

    return pkts_per_dispatch(run)
