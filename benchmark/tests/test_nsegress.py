"""The namespaced-egress cell on the CPU: a whole run at the debug sizes
reading ``correct``, the ``no_deny`` control failing the check, the
plain reference against a brute-force evaluation of the NetworkPolicy
objects packet by packet, and the cell's per-layer readers on a trace
and counters of the program and on a run that lacks them."""

import io
import ipaddress
import json
from contextlib import redirect_stdout
from pathlib import Path

import numpy as np
import pytest

from benchmark import control, run
from benchmark.gen import Generator
from benchmark.localclassify import KERNEL, mean_us
from benchmark.spec import Spec, load_module

CELL = "nsegress.podsyn.sat"
HERE = Path(__file__).resolve().parent
SYSTEM = load_module(HERE.parent / "systems" / "nsegress.py")
REF = load_module(HERE.parent / "configs" / "nsegress_reference.py")


def _cfg_mix(debug=False):
    spec = Spec()
    cell = spec.cell(CELL)
    cfg, mix = spec.config(cell["config"]), spec.traffic(cell["traffic"])
    if debug:
        cfg = run.merge(cfg, cfg["debug"])
        mix = run.merge(mix, mix["debug"])
    return cfg, mix


def _world(cfg):
    """The address plan the system module builds, without the program:
    node.py's pod addresses and interfaces, the policies' peer nodes."""
    base = int(ipaddress.ip_address(cfg["node_net"]))
    local = int(cfg["local_node"])
    pods = int(cfg["pods"])
    return {"uplink_if": 1, "gateway": base + (local << 8) + 1,
            "pod_ip": [base + (local << 8) + int(cfg["pod_host_base"]) + k
                       for k in range(pods)],
            "pod_if": list(range(pods + 1, 1, -1)),
            "peer_nodes": SYSTEM.policy_peer_nodes(cfg),
            "node_net_base": base, "vip": (0, 0)}


def test_debug_run_is_correct():
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = run.main(["--workload", CELL, "--seed", str(2**31 + 7),
                       "--seconds", "1", "--trace", "0", "--debug-cpu"])
    assert rc == 0
    line = json.loads(buf.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert sorted(line["metrics"]) == ["delivered_mpps", "setup_s"]
    assert set(line["checks"]) == {"wrong_pkts", "lost_pkts", "bad_frames"}


def test_no_deny_control_fails_the_check(capsys):
    control.main(["--workload", CELL, "--seeds", "21,22,23", "--seconds",
                  "1", "--debug-cpu"])
    rows = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert len(rows) == 3
    for r in rows:
        assert r["program"]["wrong_pkts"] == 0
        assert r["control"]["wrong_pkts"] > 0


def _brute_force(cfg, f):
    """Each packet against the Kubernetes objects the system module
    hands the agent: a pod selected by its namespace's egress policy may
    send what one of the policy's rules allows (an ipBlock holding the
    destination outside its excepts, on one of the rule's ports)."""
    namespaces, pods, policies = SYSTEM.k8s_objects(cfg, _world(cfg)["pod_ip"])
    by_ns = {p.namespace: p for p in policies}
    out = np.zeros(len(f["dst_ip"]), bool)
    for i in range(len(out)):
        pol = by_ns.get(pods[int(f["src_pod"][i])].namespace)
        if pol is None:
            out[i] = True
            continue
        dst = ipaddress.ip_address(int(f["dst_ip"][i]))
        proto = {6: "TCP", 17: "UDP"}.get(int(f["proto"][i]))
        port = int(f["dport"][i])
        out[i] = any(
            any(pp.protocol == proto and pp.port == port for pp in rule.ports)
            and any(dst in ipaddress.ip_network(pe.ip_block.cidr)
                    and not any(dst in ipaddress.ip_network(e)
                                for e in pe.ip_block.except_cidrs)
                    for pe in rule.peers)
            for rule in pol.egress_rules)
    return out


@pytest.mark.parametrize("debug", [True, False])
def test_reference_matches_brute_force(debug):
    cfg, mix = _cfg_mix(debug)
    world = _world(cfg)
    gen = Generator(mix, world, 2**33 + 5)
    f = gen.fields(np.arange(6000, dtype=np.uint64))
    # widen the ports and protocol so both sides of every test are hit
    rng = np.random.default_rng(1)
    f["dport"] = np.where(rng.random(6000) < 0.2,
                          rng.integers(7990, 8030, 6000), f["dport"])
    f["proto"] = np.where(rng.random(6000) < 0.1, 17, f["proto"])
    ref = REF.Reference(cfg, world)
    want = _brute_force(cfg, f)
    got = ref.expected(f)["disp"] != 0
    np.testing.assert_array_equal(got, want)
    assert (REF.Reference(cfg, world, control="no_deny").expected(f)["disp"]
            != 0).all()
    if not debug:
        # the permitted share of the cell's own traffic (about 39%)
        f = gen.fields(np.arange(200000, dtype=np.uint64))
        share = float((ref.expected(f)["disp"] != 0).mean())
        assert 0.35 < share < 0.43, share


def test_local_classify_us_reads_the_kernel_events():
    evs = [[f"{KERNEL}.2", 0.0, 3000.0, ""], [f"{KERNEL}.2", 9.0, 4000.0, ""],
           # the global kernel, and a fusion that names the kernel, are not it
           ["bv_first_set.1", 0.0, 9000.0, ""],
           [f"fusion.{KERNEL}", 0.0, 9000.0, ""]]
    run_ = {"trace": {"devices": {"tpu": evs}, "host": []}}
    assert mean_us(run_) == pytest.approx(3.5)
    assert mean_us({"trace": {"devices": {"tpu": evs[2:]}, "host": []}}) is None
    assert mean_us({}) is None


def test_cell_readers_read_none_without_their_sources():
    """Every per-layer reader of the cell reads a traced run of this
    program, and reads None (never raises) on a program or run that
    lacks its counter, span or trace."""
    spec = Spec()
    readers = dict((m["name"], mod) for m, mod in spec.metrics(CELL, True))
    names = {m["name"] for m in json.loads(
        (HERE.parent.parent / "BENCHMARK.json").read_text())["per_layer"]
        if CELL in m.get("workloads", ())}
    assert names <= set(readers)
    stats0 = {"pkts": 0, "batches": 0, "t_pack": 0.0, "t_dispatch": 0.0,
              "t_fetch": 0.0, "t_write": 0.0, "fastpath_hits": 0,
              "t_dispatch_cpu": 0.0, "t_dp_call": 0.0, "local_table_pkts": 0}
    stats1 = dict(stats0, pkts=2048 * 30, batches=30, t_pack=0.03,
                  t_dispatch=0.6, t_fetch=0.06, t_write=0.03,
                  t_dispatch_cpu=0.54, t_dp_call=0.45,
                  local_table_pkts=2048 * 26)
    full = {"stats0": stats0, "stats1": stats1,
            "busy": {"idle_pct": 0.21},
            "rungs": {"policy_render_s": 5.8},
            "trace": {"devices": {"tpu": [[f"{KERNEL}.2", 0.0, 3200.0, ""]]},
                      "host": []}}
    got = {n: readers[n].read(full) for n in names}
    assert got == pytest.approx({
        "local_table_pkt_share.podsyn": 100 * 26 / 30,
        "local_classify_us.podsyn": 3.2, "policy_render_s.podsyn": 5.8,
        "device_idle_pct.podsyn": 0.21, "step_call_ms.podsyn": 15.0,
        "dispatch_cpu_pct.podsyn": 90.0,
        "pump_host_us_per_pkt.podsyn": 0.72 / (2048 * 30) * 1e6})
    older = {k: v for k, v in stats0.items()
             if k not in ("t_dispatch_cpu", "t_dp_call", "local_table_pkts")}
    bare = {"stats0": older, "stats1": dict(older, pkts=0, batches=0),
            "busy": None, "rungs": {}, "trace": None}
    for n in names:
        assert readers[n].read(bare) is None, n
