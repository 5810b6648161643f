"""Pump layer, pod-to-pod cell: the dispatch thread's CPU time over its
dispatch calls as a share of their wall time (``t_dispatch_cpu`` /
``t_dispatch``, span ``pump.dispatch``). Higher is better: a low value
means the call waits (for the GIL, a lock, a blocking transfer)
instead of working."""


def read(run):
    from benchmark.stagestats import dispatch_cpu_pct

    return dispatch_cpu_pct(run)
