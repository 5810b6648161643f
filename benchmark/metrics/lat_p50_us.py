"""Median ring-to-ring latency of every frame due in the window, from its
due time on the open-loop schedule to its drain from the tx ring."""


def read(run):
    import numpy as np

    lat = run.get("lat_us")
    if lat is None or not len(lat):
        return None
    return float(np.percentile(lat, 50))
