"""Pump layer, namespaced-egress cell: the dispatch thread's CPU share
of its dispatch calls, as in ``dispatch_cpu_pct.64B``."""


def read(run):
    from benchmark.stagestats import dispatch_cpu_pct

    return dispatch_cpu_pct(run)
