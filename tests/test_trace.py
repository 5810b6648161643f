"""Packet tracer tests.

Reference model: VPP `trace add` / `show trace` behavior — capture N
packets, show per-node path including drop point
(docs/VPP_PACKET_TRACING_K8S.md:20-50). The `show run` analog (the
fused step's per-stage named scopes, the pump's host stage spans) is
tested in tests/test_stage_timing.py.
"""

import ipaddress

from vpp_tpu.ir import Action, ContivRule, Protocol
from vpp_tpu.pipeline.dataplane import Dataplane
from vpp_tpu.pipeline.tables import DataplaneConfig
from vpp_tpu.pipeline.vector import Disposition, ip4, make_packet_vector
from vpp_tpu.trace import PacketTracer


def wired_dp():
    dp = Dataplane(DataplaneConfig(sess_slots=256))
    uplink = dp.add_uplink()
    a = dp.add_pod_interface(("default", "a"))
    b = dp.add_pod_interface(("default", "b"))
    dp.builder.add_route("10.1.1.2/32", a, Disposition.LOCAL)
    dp.builder.add_route("10.1.1.3/32", b, Disposition.LOCAL)
    dp.builder.add_route("10.2.0.0/16", uplink, Disposition.REMOTE,
                         next_hop=ip4("192.168.16.2"), node_id=2)
    slot = dp.alloc_table_slot("t")
    dp.builder.set_local_table(slot, [
        ContivRule(action=Action.PERMIT,
                   dest_network=ipaddress.ip_network("10.1.1.3/32"),
                   protocol=Protocol.TCP, dest_port=80),
        ContivRule(action=Action.PERMIT,
                   dest_network=ipaddress.ip_network("10.2.0.0/16")),
        ContivRule(action=Action.DENY),
    ])
    dp.assign_pod_table(("default", "a"), "t")
    # VIP NAT for the dnat path
    dp.builder.set_nat_mapping(0, ext_ip=ip4("10.96.0.1"), ext_port=80,
                               proto=6, backends=[(ip4("10.1.1.3"), 80, 1)],
                               boff=0)
    dp.swap()
    return dp, a, b, uplink


def test_trace_paths_and_arming():
    dp, a, b, uplink = wired_dp()
    tracer = PacketTracer()
    assert tracer.record(dp.process(make_packet_vector(
        [dict(src="10.1.1.2", dst="10.1.1.3", proto=6, sport=1, dport=80,
              rx_if=a)]))) == 0, "not armed: nothing captured"

    tracer.add(10)
    frame = make_packet_vector([
        dict(src="10.1.1.2", dst="10.1.1.3", proto=6, sport=2, dport=80, rx_if=a),   # local ok
        dict(src="10.1.1.2", dst="10.1.1.3", proto=6, sport=3, dport=22, rx_if=a),   # acl deny
        dict(src="10.1.1.2", dst="10.2.9.9", proto=6, sport=4, dport=80, rx_if=a),   # remote
        dict(src="10.1.1.2", dst="10.96.0.1", proto=6, sport=5, dport=80, rx_if=a),  # via VIP
        dict(src="10.1.1.2", dst="10.9.9.9", proto=6, sport=6, dport=80, rx_if=a),   # no route→deny(acl)
        dict(src="10.1.1.2", dst="10.1.1.3", proto=6, sport=7, dport=80, ttl=0, rx_if=a),  # ttl drop
    ])
    captured = tracer.record(dp.process(frame))
    assert captured == 6
    e = tracer.entries()
    assert "interface-output (if %d)" % b in e[0].path
    assert e[1].drop_cause == "acl-deny"
    assert "error-drop (acl-deny)" in e[1].path
    assert "vxlan/ici-encap" in e[2].path and e[2].disposition == "REMOTE"
    assert "nat44-dnat" in e[3].path and e[3].dst == "10.1.1.3"
    assert e[4].drop_cause == "acl-deny"  # denied before lookup
    assert e[5].drop_cause == "ip4-input"
    assert "error-drop (ip4-input)" in e[5].path

    text = tracer.format_trace()
    assert "10.1.1.2 -> 10.1.1.3" in text
    assert "acl-deny" in text


def test_trace_established_return_flow():
    dp, a, b, uplink = wired_dp()
    dp.process(make_packet_vector(
        [dict(src="10.1.1.2", dst="10.1.1.3", proto=6, sport=999, dport=80,
              rx_if=a)]
    ))
    tracer = PacketTracer()
    tracer.add(1)
    res = dp.process(make_packet_vector(
        [dict(src="10.1.1.3", dst="10.1.1.2", proto=6, sport=80, dport=999,
              rx_if=b)]
    ))
    tracer.record(res)
    (e,) = tracer.entries()
    assert "session-lookup (established)" in e.path
    assert e.disposition == "LOCAL"


def test_trace_arming_counts_down_across_frames():
    dp, a, b, uplink = wired_dp()
    tracer = PacketTracer()
    tracer.add(3)
    frame = make_packet_vector([
        dict(src="10.1.1.2", dst="10.1.1.3", proto=6, sport=10 + i, dport=80,
             rx_if=a) for i in range(2)
    ])
    assert tracer.record(dp.process(frame)) == 2
    assert tracer.record(dp.process(frame)) == 1, "only 1 left armed"
    assert tracer.record(dp.process(frame)) == 0
    assert len(tracer.entries()) == 3
    tracer.clear()
    assert tracer.entries() == []


def test_dataplane_auto_records_when_tracer_attached():
    dp, a, b, uplink = wired_dp()
    tracer = PacketTracer()
    dp.tracer = tracer
    tracer.add(2)
    dp.process(make_packet_vector(
        [dict(src="10.1.1.2", dst="10.1.1.3", proto=6, sport=1, dport=80,
              rx_if=a)]
    ))
    assert len(tracer.entries()) == 1
