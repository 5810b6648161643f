"""Device boundary, pod-to-pod cell: device-to-host bytes per delivered
packet, as in ``boundary_bytes_per_pkt.sat``."""


def read(run):
    if run["delivered_pkts"] <= 0:
        return None
    return run["xfer_bytes"] / run["delivered_pkts"]
