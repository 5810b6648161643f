"""Device boundary, pod-to-pod cell: host milliseconds per dispatch
turning the packed batch and the scalars into device arrays inside
``Dataplane.process_packed`` (span ``dp.upload``; counter
``t_dp_upload``)."""


def read(run):
    from benchmark.stagestats import ms_per_batch

    return ms_per_batch(run, "t_dp_upload")
