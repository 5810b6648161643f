"""Device boundary, namespaced-egress cell: host milliseconds per
dispatch in the dataplane's jitted step call, as in
``step_call_ms.64B``."""


def read(run):
    from benchmark.stagestats import ms_per_batch

    return ms_per_batch(run, "t_dp_call")
