"""Kernels, namespaced-egress cell: mean device time of one call of the
local tables' first-set kernel (``acl_local_bv_first_set``) in the
traced slice, in us."""


def read(run):
    from benchmark.localclassify import mean_us

    return mean_us(run)
