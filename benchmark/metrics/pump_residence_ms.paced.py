"""Pump layer, paced cell: mean milliseconds a frame spends inside the
pump, from the dispatch thread's take off the rx ring to its tx commit
(counter ``t_resident``, frame-weighted, over ``frames``). The rest of
the ring-to-ring latency is the wait in the rx ring before the take."""


def read(run):
    from benchmark.stagestats import ratio

    return ratio(run, "t_resident", "frames", 1e3)
