"""Pump layer: serial host time (pack + dispatch + fetch copy + tx write)
per packet, from the pump's stage timers over the window."""


def read(run):
    from benchmark.pumpstats import host_us_per_pkt

    return host_us_per_pkt(run)
