"""Pump layer, namespaced-egress cell: serial host time (pack + dispatch
+ fetch copy + tx write) per packet over the window, as in
``pump_host_us_per_pkt.sat``."""


def read(run):
    from benchmark.pumpstats import host_us_per_pkt

    return host_us_per_pkt(run)
