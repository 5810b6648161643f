"""Two-tier established-flow fast path: differential equivalence.

The dispatch contract (pipeline/graph.py pipeline_step_auto): a batch
where EVERY valid packet hits a live reflective session (and none
DNAT-matches) runs a classify-free kernel; everything else falls
through to the full chain unchanged. These tests prove the contract
the only way that matters — bit-exact output equality against the
always-full-chain reference on identical inputs and identical session
state, across mixed established/fresh/deny traffic, plus the positive
proof that an all-established batch actually takes the fast kernel
(StepStats.fastpath == 1, the runtime branch signal).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from vpp_tpu.ir.rule import Action, ContivRule, Protocol
from vpp_tpu.pipeline.dataplane import (
    Dataplane,
    pack_packet_columns,
    unpack_packet_result,
)
from vpp_tpu.pipeline.graph import (
    pipeline_step,
    pipeline_step_auto,
    pipeline_step_fast,
)
from vpp_tpu.pipeline.tables import SESSION_FIELDS, DataplaneConfig
from vpp_tpu.pipeline.vector import (
    FLAG_VALID,
    Disposition,
    ip4,
    make_packet_vector,
)

VIP = "10.96.0.1"


def build_dp(**over):
    cfg = DataplaneConfig(
        max_tables=2, max_rules=16, max_global_rules=32, max_ifaces=8,
        fib_slots=16, sess_slots=256, nat_mappings=2, nat_backends=2,
        **over,
    )
    dp = Dataplane(cfg)
    up = dp.add_uplink()
    pod = dp.add_pod_interface(("default", "web"))
    dp.builder.add_route("10.1.1.0/24", pod, Disposition.LOCAL)
    dp.builder.add_route("0.0.0.0/0", up, Disposition.REMOTE, node_id=1)
    dp.builder.set_global_table([
        ContivRule(action=Action.PERMIT, protocol=Protocol.TCP,
                   dest_port=80),
        ContivRule(action=Action.PERMIT, protocol=Protocol.TCP,
                   dest_port=8080),
        ContivRule(action=Action.DENY),
    ])
    # service VIP with one local backend (exercises DNAT + NAT session)
    dp.builder.set_nat_mapping(
        0, ext_ip=ip4(VIP), ext_port=80, proto=6,
        backends=[(ip4("10.1.1.2"), 8080, 1)], boff=0,
    )
    dp.swap()
    return dp, up, pod


def assert_results_equal(ref, got, *, expect_fast):
    """Field-for-field StepResult equality: dispositions, rewrites,
    attribution, session-table state, and every counter except the
    fastpath branch flag itself (the one designed difference)."""
    for f in ("disp", "tx_if", "node_id", "next_hop", "drop_cause",
              "established", "dnat_applied", "snat_applied"):
        assert np.array_equal(
            np.asarray(getattr(ref, f)), np.asarray(getattr(got, f))
        ), f"StepResult.{f} diverged"
    for f in ref.pkts._fields:
        assert np.array_equal(
            np.asarray(getattr(ref.pkts, f)),
            np.asarray(getattr(got.pkts, f)),
        ), f"pkts.{f} diverged (header rewrite mismatch)"
    for f in ref.stats._fields:
        if f == "fastpath":
            continue
        assert np.array_equal(
            np.asarray(getattr(ref.stats, f)),
            np.asarray(getattr(got.stats, f)),
        ), f"stats.{f} diverged"
    # touched session slots (timestamps included) must be identical —
    # the fast path's touch discipline is part of the contract
    for f in SESSION_FIELDS:
        assert np.array_equal(
            np.asarray(getattr(ref.tables, f)),
            np.asarray(getattr(got.tables, f)),
        ), f"tables.{f} diverged (session state mismatch)"
    assert int(got.stats.fastpath) == (1 if expect_fast else 0), (
        f"expected fastpath={'1' if expect_fast else '0'}, got "
        f"{int(got.stats.fastpath)}"
    )


def mixed_traffic(up, n=16):
    """Fresh permitted + fresh denied + VIP (DNAT) + invalid slots."""
    return make_packet_vector([
        {"src": "172.16.0.5", "dst": "10.1.1.7", "proto": 6,
         "sport": 4001, "dport": 80, "rx_if": up},
        {"src": "172.16.0.6", "dst": "10.1.1.8", "proto": 6,
         "sport": 4002, "dport": 80, "rx_if": up},
        {"src": "172.16.0.7", "dst": "10.1.1.9", "proto": 6,
         "sport": 4003, "dport": 9999, "rx_if": up},  # denied
        {"src": "172.16.0.8", "dst": VIP, "proto": 6,
         "sport": 4004, "dport": 80, "rx_if": up},    # DNAT'd
    ], n=n)


def replies_for(res, pod, n=16):
    """Reply vector for every forwarded packet of a step result: swap
    the POST-NAT endpoints (that is what the wire carries), ingress on
    the egress interface."""
    fwd = np.asarray(res.disp) != int(Disposition.DROP)
    pk = res.pkts
    pkts = []
    for i in np.nonzero(fwd)[0]:
        i = int(i)
        pkts.append({
            "src": int(np.asarray(pk.dst_ip)[i]),
            "dst": int(np.asarray(pk.src_ip)[i]),
            "proto": int(np.asarray(pk.proto)[i]),
            "sport": int(np.asarray(pk.dport)[i]),
            "dport": int(np.asarray(pk.sport)[i]),
            "rx_if": int(np.asarray(res.tx_if)[i]),
        })
    assert pkts, "no forwarded packets to build replies from"
    return make_packet_vector(pkts, n=n)


@pytest.fixture(scope="module")
def steps():
    return (jax.jit(pipeline_step), jax.jit(pipeline_step_auto),
            jax.jit(pipeline_step_fast))


class TestDifferential:
    def test_mixed_traffic_takes_full_chain_bit_exact(self, steps):
        step_full, step_auto, _ = steps
        dp, up, _pod = build_dp()
        pkts = mixed_traffic(up)
        ref = step_full(dp.tables, pkts, jnp.int32(5))
        got = step_auto(dp.tables, pkts, jnp.int32(5))
        # fresh flows present -> the predicate must fall through
        assert_results_equal(ref, got, expect_fast=False)
        # sanity on the mix itself: something forwarded, something
        # denied, something DNAT'd
        assert int(ref.stats.tx) >= 3
        assert int(ref.stats.drop_acl) == 1
        assert int(ref.stats.dnat) == 1

    def test_all_established_takes_classify_free_kernel(self, steps):
        step_full, step_auto, step_fast = steps
        dp, up, pod = build_dp()
        pkts = mixed_traffic(up)
        r1 = step_full(dp.tables, pkts, jnp.int32(5))
        rep = replies_for(r1, pod)
        ref = step_full(r1.tables, rep, jnp.int32(6))
        got = step_auto(r1.tables, rep, jnp.int32(6))
        # the positive proof: the classify-free kernel ran...
        assert_results_equal(ref, got, expect_fast=True)
        # ...and the batch really was established end to end: every
        # valid reply forwarded, the DNAT'd flow's reply un-NAT'd
        n_valid = int(np.asarray(rep.valid).sum())
        assert int(ref.stats.tx) == n_valid
        assert int(ref.stats.nat_reversed) == 1
        assert int(got.stats.sess_hits) == n_valid
        # the standalone fast kernel agrees too
        raw = step_fast(r1.tables, rep, jnp.int32(6))
        assert np.array_equal(np.asarray(raw.disp), np.asarray(ref.disp))
        assert int(raw.stats.fastpath) == 1

    @pytest.mark.slow  # ~27 s: partial-hit compile of both chain forms; mixed-traffic full-chain bit-exact stays the fast differential anchor
    def test_partial_hit_batch_falls_through(self, steps):
        """One fresh flow mixed into established replies: the batch
        dispatch predicate must reject and the full chain must install
        the fresh session — outputs identical to the reference."""
        step_full, step_auto, _ = steps
        dp, up, pod = build_dp()
        pkts = mixed_traffic(up)
        r1 = step_full(dp.tables, pkts, jnp.int32(5))
        rep = replies_for(r1, pod, n=8)
        # graft one fresh (never-seen) flow into the reply batch
        flags = np.asarray(rep.flags).copy()
        src = np.asarray(rep.src_ip).copy()
        dst = np.asarray(rep.dst_ip).copy()
        sport = np.asarray(rep.sport).copy()
        dport = np.asarray(rep.dport).copy()
        rx_if = np.asarray(rep.rx_if).copy()
        slot = int(np.asarray(rep.valid).sum())
        assert flags[slot] == 0
        flags[slot] = FLAG_VALID
        src[slot] = ip4("172.16.9.9")
        dst[slot] = ip4("10.1.1.30")
        sport[slot], dport[slot] = 5005, 80
        rx_if[slot] = up
        rep = rep._replace(
            flags=jnp.asarray(flags), src_ip=jnp.asarray(src),
            dst_ip=jnp.asarray(dst), sport=jnp.asarray(sport),
            dport=jnp.asarray(dport), rx_if=jnp.asarray(rx_if),
        )
        ref = step_full(r1.tables, rep, jnp.int32(6))
        got = step_auto(r1.tables, rep, jnp.int32(6))
        assert_results_equal(ref, got, expect_fast=False)
        # the fresh flow's session WAS installed by both paths
        assert int(ref.stats.sess_hits) == slot  # the established ones

    def test_established_but_dnat_matching_reply_falls_through(self, steps):
        """The subtle predicate clause: a reply that rides a reflective
        session AND whose (un-NAT'd) destination matches a DNAT mapping
        must take the full chain — the full chain translates it and
        records NAT state the fast kernel elides. Constructed by making
        the forward flow originate FROM the VIP address on the mapping
        port, so the reply targets VIP:80 exactly."""
        step_full, step_auto, _ = steps
        dp, up, pod = build_dp()
        fwd = make_packet_vector([
            {"src": VIP, "dst": "10.1.1.7", "proto": 6,
             "sport": 80, "dport": 8080, "rx_if": up},
        ], n=8)
        r1 = step_full(dp.tables, fwd, jnp.int32(5))
        assert int(r1.stats.tx) == 1
        rep = make_packet_vector([
            {"src": "10.1.1.7", "dst": VIP, "proto": 6,
             "sport": 8080, "dport": 80, "rx_if": pod},
        ], n=8)
        ref = step_full(r1.tables, rep, jnp.int32(6))
        got = step_auto(r1.tables, rep, jnp.int32(6))
        # established (reflective hit) but DNAT-matching -> full chain
        assert bool(np.asarray(ref.established)[0])
        assert bool(np.asarray(ref.dnat_applied)[0])
        assert_results_equal(ref, got, expect_fast=False)

    def test_expired_sessions_fall_through(self, steps):
        """Sessions past sess_max_age are dead for the predicate too:
        the 'reply' is then a fresh flow and must take the full chain
        (where the ACL decides its fate)."""
        step_full, step_auto, _ = steps
        dp, up, pod = build_dp()
        pkts = mixed_traffic(up)
        r1 = step_full(dp.tables, pkts, jnp.int32(5))
        rep = replies_for(r1, pod)
        late = jnp.int32(5 + int(dp.config.sess_max_age) + 1)
        ref = step_full(r1.tables, rep, late)
        got = step_auto(r1.tables, rep, late)
        assert int(ref.stats.sess_hits) == 0
        assert_results_equal(ref, got, expect_fast=False)


class TestPackedAux:
    @pytest.mark.slow  # ~16 s: packed-aux variant compile; aux schema width parity stays fast in test_telemetry
    def test_packed_aux_reports_fast_dispatch(self):
        """The pump-facing telemetry: process_packed(with_aux=True)
        returns [fastpath, rx, sess_hits] from the same program, and
        the packed outputs stay identical to a fastpath-disabled
        dataplane fed the same batch."""
        dp, up, pod = build_dp()
        dp_ref, up2, pod2 = build_dp(fastpath=False)
        assert dp._use_fastpath and not dp_ref._use_fastpath
        step_full = jax.jit(pipeline_step)

        pkts = mixed_traffic(up)
        r1 = step_full(dp.tables, pkts, jnp.int32(5))
        rep = replies_for(r1, pod, n=8)
        cols = {
            f: np.asarray(getattr(rep, f))
            for f in ("src_ip", "dst_ip", "proto", "sport", "dport",
                      "ttl", "pkt_len", "rx_if", "flags")
        }
        flat = np.zeros((5, 8), np.int32)
        pack_packet_columns(flat.view(np.uint32), cols, 8)

        # both dataplanes primed with the identical forward step
        dp.tables = r1.tables
        dp_ref.tables = step_full(dp_ref.tables, pkts, jnp.int32(5)).tables

        out, aux = dp.process_packed(flat.copy(), now=6, with_aux=True)
        a = np.asarray(jax.device_get(aux))
        assert a[0] == 1, "all-established packed batch not fast-dispatched"
        n_valid = int(np.asarray(rep.valid).sum())
        assert a[1] == n_valid and a[2] == n_valid
        ref_out = dp_ref.process_packed(flat.copy(), now=6)
        got = unpack_packet_result(np.array(jax.device_get(out)))
        want = unpack_packet_result(np.array(jax.device_get(ref_out)))
        for k in want:
            assert np.array_equal(got[k], want[k]), k

    def test_disabled_fastpath_still_measures_regime(self):
        """With the fast path disengaged the full chain still reports
        the aux summary (fastpath=0, hits/alive measured) — the
        hit-percentage gauge must diagnose the disengaged regime, not
        read as 'no established traffic'."""
        import jax as _jax

        dp, up, _pod = build_dp(fastpath=False)
        from vpp_tpu.pipeline.dataplane import packed_input_zeros

        out, aux = dp.process_packed(packed_input_zeros(8), with_aux=True)
        a = np.asarray(_jax.device_get(aux))
        assert a[0] == 0 and a[1] == 0 and a[2] == 0

    def test_min_rules_threshold_gates_engagement(self):
        dp, up, _pod = build_dp(fastpath_min_rules=1000)
        assert dp.fastpath_enabled
        assert not dp._use_fastpath  # 3 global rules < 1000


class TestPumpWire:
    def test_pump_counts_fastpath_batches_on_reply_traffic(self):
        """End-to-end regime wiring: real wire frames through the
        dispatch pump. The fresh forward flow takes the full chain
        (fastpath_batches stays 0), its reply rides the reflective
        session and must be counted as a fast-dispatched batch with
        hit accounting behind the fastpath_hit_pct gauge."""
        import time as _time

        from wire import make_frame

        from vpp_tpu.io import (
            DataplanePump,
            IODaemon,
            IORingPair,
            SocketPairTransport,
        )

        dp, up, pod = build_dp()
        client_if = dp.add_pod_interface(("default", "client"))
        dp.builder.add_route("10.1.1.9/32", client_if, Disposition.LOCAL)
        dp.swap()
        # compile the packed auto kernel BEFORE wire traffic: the recv
        # timeouts must measure the data path, not the first jit trace
        from vpp_tpu.pipeline.dataplane import packed_input_zeros

        dp.process_packed(packed_input_zeros(256))
        rings = IORingPair(n_slots=8)
        transports = {}
        outside = {}
        for if_idx, name in ((client_if, "client"), (pod, "server")):
            inside, out = SocketPairTransport.pair(name)
            transports[if_idx] = inside
            outside[name] = out
        daemon = IODaemon(rings, transports, uplink_if=up).start()
        pump = DataplanePump(dp, rings).start()
        try:
            def recv(name, timeout=10.0):
                sock = outside[name].sock
                sock.setblocking(True)
                sock.settimeout(timeout)
                try:
                    return sock.recv(65535)
                finally:
                    sock.setblocking(False)

            # fresh forward flow client -> server pod (permitted: tcp/80)
            outside["client"].send_frame(make_frame(
                "10.1.1.9", "10.1.1.7", proto=6, sport=4001, dport=80))
            recv("server")
            assert pump.stats["fastpath_batches"] == 0
            assert pump.stats["fastpath_alive"] >= 1
            # the reply rides the reflective session -> fast dispatch
            outside["server"].send_frame(make_frame(
                "10.1.1.7", "10.1.1.9", proto=6, sport=80, dport=4001))
            recv("client")
            deadline = _time.monotonic() + 5
            while _time.monotonic() < deadline and \
                    pump.stats["fastpath_batches"] == 0:
                _time.sleep(0.01)
            assert pump.stats["fastpath_batches"] >= 1
            assert pump.stats["fastpath_hits"] >= 1
        finally:
            pump.stop()
            daemon.stop()
            for t in transports.values():
                t.close()
            for t in outside.values():
                t.close()
            rings.close()
