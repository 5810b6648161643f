"""The readers of the pump's stage counters, on synthetic window
snapshots: each is a window delta over its denominator, None where the
denominator is 0 and where the program lacks the counter."""

from pathlib import Path

import pytest

from benchmark.spec import Spec

ROOT = Path(__file__).resolve().parents[2]

BASE = {"frames": 100, "pkts": 25_600, "batches": 40, "t_dispatch": 2.0,
        "t_dispatch_cpu": 1.0, "t_dp_upload": 0.1, "t_dp_call": 1.5,
        "t_fetch_queue": 0.2, "t_reorder_wait": 0.05, "t_resident": 3.0,
        "rx_backlog_sum": 120}
# the window: 50 frames in 20 dispatches
STEP = {"frames": 50, "pkts": 12_800, "batches": 20, "t_dispatch": 0.4,
        "t_dispatch_cpu": 0.1, "t_dp_upload": 0.02, "t_dp_call": 0.3,
        "t_fetch_queue": 0.06, "t_reorder_wait": 0.01, "t_resident": 1.5,
        "rx_backlog_sum": 70}
WANT = {
    "step_call_ms.64B": 15.0,        # 0.3 s / 20 dispatches
    "step_upload_ms.64B": 1.0,
    "dispatch_cpu_pct.64B": 25.0,    # 0.1 / 0.4
    "step_call_ms.sat": 15.0,
    "dispatch_cpu_pct.sat": 25.0,
    "pump_residence_ms.paced": 30.0,  # 1.5 s / 50 frames
    "fetch_queue_ms.paced": 3.0,
    "reorder_wait_ms.paced": 0.5,
    "rx_backlog_frames.paced": 3.5,   # 70 / 20
}


def readers():
    spec = Spec(ROOT)
    out = {}
    for cell in spec.doc["workloads"]:
        for m, reader in spec.metrics(cell["name"], True):
            if m["name"] in WANT:
                out[m["name"]] = reader
    return out


def window(step):
    return {"stats0": dict(BASE),
            "stats1": {k: BASE[k] + step.get(k, 0) for k in BASE}}


def test_every_metric_has_a_reader_in_its_cell():
    assert set(readers()) == set(WANT)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_is_a_window_delta(name):
    assert readers()[name].read(window(STEP)) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_is_none_on_an_empty_window(name):
    assert readers()[name].read(window({})) is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_is_none_without_the_counters(name):
    """A program older than the counters (the parent commit) reports
    nothing, and does not raise."""
    old = {k: v for k, v in BASE.items()
           if k in ("frames", "pkts", "batches", "t_dispatch")}
    run = {"stats0": old,
           "stats1": {k: v + STEP[k] for k, v in old.items()}}
    assert readers()[name].read(run) is None
