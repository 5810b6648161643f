"""Packets the tx ring returned during the window, with any verdict,
over the window, in millions per second."""


def read(run):
    if run["seconds"] <= 0:
        return None
    return run["delivered_pkts"] / run["seconds"] / 1e6
