"""Device boundary: device-to-host bytes the transfer guard counted over
the window, per delivered packet (padding to a bucket shows here)."""


def read(run):
    if run["delivered_pkts"] <= 0:
        return None
    return run["xfer_bytes"] / run["delivered_pkts"]
