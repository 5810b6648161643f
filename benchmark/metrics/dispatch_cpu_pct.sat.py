"""Pump layer, node5k saturated cell: the dispatch thread's CPU share of
its dispatch calls, as in ``dispatch_cpu_pct.64B``. Higher is better:
a low value means the call waits instead of working."""


def read(run):
    from benchmark.stagestats import dispatch_cpu_pct

    return dispatch_cpu_pct(run)
