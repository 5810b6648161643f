"""Pump layer, pod-to-pod cell: packets per device dispatch, as in
``pump_pkts_per_dispatch.sat``."""


def read(run):
    from benchmark.pumpstats import pkts_per_dispatch

    return pkts_per_dispatch(run)
