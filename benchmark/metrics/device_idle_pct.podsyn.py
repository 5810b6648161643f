"""Device, namespaced-egress cell: idle share of the traced slice, as in
``device_idle_pct.sat``."""


def read(run):
    busy = run.get("busy")
    return None if busy is None else busy["idle_pct"]
