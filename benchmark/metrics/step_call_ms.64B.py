"""Device boundary, pod-to-pod cell: host milliseconds per dispatch in
the dataplane's jitted step call (``Dataplane.process_packed``, span
``dp.step_call``; counter ``t_dp_call``). Dispatch is asynchronous, so
this is the host's side of the call, not the device step."""


def read(run):
    from benchmark.stagestats import ms_per_batch

    return ms_per_batch(run, "t_dp_call")
