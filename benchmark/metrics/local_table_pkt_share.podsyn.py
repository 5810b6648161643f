"""Fused step, namespaced-egress cell: share of the dispatched packets
whose rx interface points at a local ACL table, window deltas of the
pump's ``local_table_pkts`` over ``pkts``, in percent."""


def read(run):
    from benchmark.stagestats import ratio

    return ratio(run, "local_table_pkts", "pkts", 100.0)
