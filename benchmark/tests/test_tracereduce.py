"""The trace reduction on small traces: union not sum, idle share,
per-kernel time by name, the top-level op table and gap attribution."""

import json
from pathlib import Path

import pytest

from benchmark import tracereduce as tr

DATA = Path(__file__).parent / "data"


def trace(devices, host=()):
    return {"devices": devices, "host": [list(h) for h in host]}


def test_nested_events_count_once():
    # a 100 ns while loop holding two 30 ns ops, then a 50 ns op after a
    # 50 ns gap: busy is 150 ns of 200, not the 210 a sum would give
    t = trace({"/device:TPU:0": [["while", 0, 100, ""], ["a", 10, 30, ""],
                                 ["b", 50, 30, ""], ["c", 150, 50, ""]]})
    b = tr.device_busy(t)
    assert b["window_s"] == pytest.approx(200e-9)
    assert b["busy_s"] == pytest.approx(150e-9)
    assert b["idle_pct"] == pytest.approx(25.0)


def test_overlap_is_merged_and_clipped():
    assert tr.union([(0, 10), (5, 20), (30, 40)]) == [[0, 20], [30, 40]]
    assert tr.busy_ns([["x", -10, 30, ""]], 0, 10) == 10


def test_idle_is_the_mean_over_devices():
    t = trace({"/device:TPU:0": [["x", 0, 100, ""]],
               "/device:TPU:1": [["x", 0, 50, ""]]})
    assert tr.device_busy(t)["idle_pct"] == pytest.approx(25.0)
    assert tr.device_busy(t)["devices"] == 2


def test_no_device_ops_reads_nothing():
    assert tr.device_busy(trace({"/device:TPU:0": []})) is None
    assert tr.idle_gaps(trace({})) == []


def test_kernel_time_by_name_and_top_level_ops():
    t = trace({"/device:TPU:0": [["while.1", 0, 100, ""],
                                 ["bv_first_set.3", 10, 20, "m"],
                                 ["bv_first_set.3", 40, 20, "m"],
                                 ["fusion", 120, 30, ""]]})
    ev = tr.named_events(t, "bv_first_set")
    assert sum(e[2] for e in ev) == 40
    top = dict(tr.top_ops(t))
    assert set(top) == {"while.1", "fusion"}
    assert top["while.1"] == pytest.approx(100e-9)


def test_gaps_named_by_the_host_span_that_covers_them():
    t = trace({"/device:TPU:0": [["x", 0, 10, ""], ["y", 100, 10, ""],
                                 ["z", 130, 10, ""]]},
              host=[("load.drain", 5, 90), ("load.push", 131, 2)])
    gaps = tr.idle_gaps(t)
    assert gaps[0][0] == "load.drain"
    assert gaps[0][1] == pytest.approx(90e-9)
    assert gaps[1] == ["unattributed", pytest.approx(20e-9)]


def test_recorded_chip_trace():
    """The events that start in 30 ms from the middle of a trace recorded
    on a v5e serving node5k.newflow.sat, normalised:
    the union of its op intervals is below their plain sum (the while
    loops nest ops), and the reduction reads a busy share in (0, 100]."""
    path = DATA / "node5k_sat_slice.json"
    t = json.loads(path.read_text())
    evs = next(iter(t["devices"].values()))
    lo, hi = tr.extent(t)
    total = sum(e[2] for e in evs)
    busy = tr.busy_ns(evs, lo, hi)
    assert 0 < busy < total
    b = tr.device_busy(t)
    assert 0 <= b["idle_pct"] < 100
    assert tr.named_events(t, "bv_first_set")
