"""Pump layer, paced cell: mean frames pending in the rx ring and not
yet taken, read just before each dispatching take (counter
``rx_backlog_sum`` over ``batches``)."""


def read(run):
    from benchmark.stagestats import ratio

    return ratio(run, "rx_backlog_sum", "batches")
