"""Device: idle share of the traced slice, 1 - union of op intervals over
the slice, averaged over the chips."""


def read(run):
    busy = run.get("busy")
    return None if busy is None else busy["idle_pct"]
