"""Whole runs on the CPU at each configuration's debug sizes: the result
line's schema, the check passing on the program as it is, the control
failing it, and the served path broken underneath making ``correct``
come out false. Each run compiles the step once on the CPU."""

import io
import json
from contextlib import redirect_stdout

import pytest

from benchmark import control, run
from benchmark.faults import PLANTS


def run_cell(cell, *extra, fault=None, seconds="1"):
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = run.main(["--workload", cell, "--seed", str(2**31 + 3),
                       "--seconds", seconds, "--trace", "0", "--debug-cpu",
                       *extra], fault=fault)
    assert rc == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("cell", ["node5k.newflow.sat",
                                  "node5k.newflow.paced",
                                  "pod2pod.64B.sat"])
def test_result_line(cell):
    line = run_cell(cell)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert "setup_s" in line["metrics"]
    want = {"node5k.newflow.sat": ["delivered_mpps"],
            "node5k.newflow.paced": ["lat_p50_us"],
            "pod2pod.64B.sat": ["delivered_mpps.64B"]}[cell]
    assert sorted(line["metrics"]) == sorted(want + ["setup_s"])
    for name in want:
        assert line["metrics"][name]["value"] > 0
    for v in line["checks"].values():
        assert set(v) == {"value", "limit"}
    # the flow probe and the backend spread exist where there is a VIP
    has_vip = cell.startswith("node5k")
    for name in ("flow_wrong_pkts", "backend_weight_gap_pct",
                 "backend_chi2"):
        assert (name in line["checks"]) == has_vip, name


@pytest.mark.parametrize("cell", ["node5k.newflow.sat", "pod2pod.64B.sat"])
def test_control_fails_the_check(cell, capsys):
    control.main(["--workload", cell, "--seeds", "11,12,13", "--seconds",
                  "1", "--debug-cpu"])
    rows = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert len(rows) == 3
    for r in rows:
        assert r["program"]["wrong_pkts"] == 0
        assert r["control"]["wrong_pkts"] > 0


@pytest.mark.parametrize("plant,cell,number", [
    ("altered", "node5k.newflow.sat", "wrong_pkts"),
    ("half", "pod2pod.64B.sat", "bad_frames"),
    ("lost", "pod2pod.64B.sat", "lost_pkts"),
    ("stale", "node5k.newflow.sat", "flow_wrong_pkts"),
    ("stale", "node5k.newflow.paced", "flow_wrong_pkts"),
    ("backend0", "node5k.newflow.sat", "backend_weight_gap_pct"),
    ("backend0", "node5k.newflow.sat", "backend_chi2"),
])
def test_planted_fault_is_not_correct(plant, cell, number):
    undo = []
    line = run_cell(cell, fault=lambda dp: undo.append(PLANTS[plant](dp)))
    for u in undo:
        u()
    assert line["correct"] is False
    assert line["checks"][number]["value"] > 0
