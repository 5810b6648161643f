"""Pump layer, pod-to-pod cell: serial host time per packet, as in
``pump_host_us_per_pkt.sat``."""


def read(run):
    from benchmark.pumpstats import host_us_per_pkt

    return host_us_per_pkt(run)
