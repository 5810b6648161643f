"""Pump layer, paced cell: milliseconds per dispatch that a dispatched
batch waits in the hand-off queue until a fetch worker takes it
(counter ``t_fetch_queue``)."""


def read(run):
    from benchmark.stagestats import ms_per_batch

    return ms_per_batch(run, "t_fetch_queue")
