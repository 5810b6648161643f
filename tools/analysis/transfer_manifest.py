"""Approved device->host fetch sites for the ``--transfers`` pass.

`TRANSFER_SITES` maps ``(repo-relative path, function qualname)`` to the
reason the fetch is sanctioned.  A qualname of ``"*"`` approves a whole
file.  Everything else that materializes a
DataplaneTables-reachable device value on host is a finding — the
"aggregate on host" regression class (PRs 6/8/12).

How to add a site (docs/STATIC_ANALYSIS.md): state WHAT bounds the
fetch (rider-sized, K candidates, drained once per epoch, ...) — "it
was convenient" is not a bound.  Entries that stop resolving are
themselves findings (``transfer-site-stale``).
"""

from typing import Dict, Tuple

TRANSFER_SITES: Dict[Tuple[str, str], str] = {
    ("vpp_tpu/pipeline/persistent.py", "PersistentPump._fetch_loop"): (
        "THE packed-result fetch: one device_get per ring window of "
        "tx/aux riders (+ telemetry rider), never table columns"),
    # --- snapshot drains (PR 8): the sanctioned bulk session fetches --
    ("vpp_tpu/pipeline/snapshot.py", "SessionSnapshotter._drain"): (
        "the periodic session checkpoint drain: amortized over the "
        "snapshot interval, runs on the snapshotter thread off the "
        "dispatch path"),
    ("vpp_tpu/pipeline/snapshot.py", "adopt_bucket_range"): (
        "live migration adopt: fetches SESSION_FIELDS once to splice "
        "the drained bucket range in; bounded by the range size and "
        "migration cadence"),
    ("vpp_tpu/pipeline/snapshot.py", "release_bucket_range"): (
        "live migration release: same bounded range splice as adopt, "
        "invalidating the moved buckets on the source"),
    # --- dataplane snapshots: bounded rider/slot-candidate fetches ----
    ("vpp_tpu/pipeline/dataplane.py", "Dataplane.fib_snapshot"): (
        "fetches fib_ecmp_c only — the ECMP counter column, slot-"
        "bounded, drained at CLI/collector cadence"),
    ("vpp_tpu/pipeline/dataplane.py", "Dataplane.telemetry_snapshot"): (
        "the telemetry rider drain: K-slot candidates + fixed "
        "histogram bins, never table columns (ISSUE 11 design)"),
    ("vpp_tpu/pipeline/dataplane.py", "Dataplane.tenant_snapshot"): (
        "per-tenant counter rows: max_tenants-bounded, collector "
        "cadence"),
    # --- operator debug drains (the VPP `show session` analogs) -------
    ("vpp_tpu/cli.py", "DebugCLI.show_session"): (
        "operator debug page: drains session columns on explicit CLI "
        "request, never on the serving path"),
    ("vpp_tpu/cli.py", "DebugCLI.show_sessions"): (
        "operator debug page: paged session table listing, explicit "
        "CLI request only"),
    ("vpp_tpu/cli.py", "DebugCLI.show_nat44"): (
        "operator debug page: NAT session listing, explicit CLI "
        "request only"),
}
