"""Pod-to-pod cell: packets the tx ring returned during the window, over
the window, in millions per second, as in ``delivered_mpps``."""


def read(run):
    if run["seconds"] <= 0:
        return None
    return run["delivered_pkts"] / run["seconds"] / 1e6
