"""Pump layer, paced cell: milliseconds per dispatch that a fetched
batch waits for the in-order tx writer to pop it (counter
``t_reorder_wait``)."""


def read(run):
    from benchmark.stagestats import ms_per_batch

    return ms_per_batch(run, "t_reorder_wait")
