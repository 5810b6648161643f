"""The packed and chained step programs return only the table leaves
the step writes (pipeline/dataplane.py ``_packed_call``).

The written set is read off the traced program: a leaf whose output is
its own input is read-only and is no output of the program, so XLA
gives it no copy and no output buffer. The host rebuilds the live
tables from the tables object the step was called with. These tests
hold the written-only programs bit-identical to the all-leaf boundary
(``_packed_tables``) over random traffic with session churn, pin the
default configuration's written set, and serve frames through the pump
while another thread reads ``dp.tables`` and an epoch swap lands.
"""

from __future__ import annotations

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from wire import make_frame

from vpp_tpu.io import DataplanePump, IORingPair
from vpp_tpu.ir.rule import Action, ContivRule, Protocol
from vpp_tpu.native.pktio import PacketCodec
from vpp_tpu.pipeline.dataplane import (
    Dataplane,
    _chained_call,
    _packed_call,
    _packed_tables,
    pack_packet_columns,
    packed_input_zeros,
)
from vpp_tpu.pipeline.graph import make_pipeline_step
from vpp_tpu.pipeline.tables import DataplaneConfig, DataplaneTables
from vpp_tpu.pipeline.vector import FLAG_VALID, VEC, Disposition, ip4

B = 64
VIP = "10.96.0.10"
SNAT = "192.168.16.1"
PODS = ["10.1.1.2", "10.1.1.3", "10.1.1.4"]

# the default configuration's written set: the ECMP member counters,
# the reflective and NAT session columns and their sweep cursors
DEFAULT_WRITES = {
    "fib_ecmp_c",
    "sess_src", "sess_dst", "sess_ports", "sess_proto", "sess_valid",
    "sess_time",
    "natsess_a", "natsess_b", "natsess_ports", "natsess_proto",
    "natsess_valid", "natsess_time", "natsess_orig_ip",
    "natsess_orig_port", "natsess_src_ip", "natsess_sport", "natsess_kind",
    "sess_sweep_cursor", "natsess_sweep_cursor",
}


def churn_dp(**over):
    """A small node with every stage live: a global table (permit
    TCP/80, UDP; deny the rest), a local table on one pod, a VIP with
    two pod backends, SNAT to the uplink, and 64-slot session tables
    with a short idle timeout, so inserts evict and entries expire."""
    cfg = DataplaneConfig(
        max_tables=2, max_rules=8, max_global_rules=16, max_ifaces=8,
        fib_slots=16, sess_slots=64, sess_max_age=40, nat_mappings=2,
        nat_backends=4, **over)
    dp = Dataplane(cfg)
    up = dp.add_uplink()
    ifs = [dp.add_pod_interface(("default", f"p{i}"))
           for i in range(len(PODS))]
    for ip, i in zip(PODS, ifs):
        dp.builder.add_route(f"{ip}/32", i, Disposition.LOCAL)
    dp.builder.add_route("0.0.0.0/0", up, Disposition.REMOTE, node_id=1)
    dp.builder.set_global_table([
        ContivRule(action=Action.PERMIT, protocol=Protocol.TCP,
                   dest_port=80),
        ContivRule(action=Action.PERMIT, protocol=Protocol.UDP),
        ContivRule(action=Action.DENY),
    ])
    slot = dp.alloc_table_slot("iso")
    dp.builder.set_local_table(slot, [
        ContivRule(action=Action.PERMIT, protocol=Protocol.TCP,
                   dest_port=8080),
        ContivRule(action=Action.DENY, protocol=Protocol.TCP)])
    dp.assign_pod_table(("default", "p2"), "iso")
    dp.builder.set_nat_mapping(
        0, ip4(VIP), 80, 6, [(ip4(PODS[0]), 8080, 1),
                             (ip4(PODS[1]), 8080, 2)], boff=0)
    dp.builder.set_snat_ip(ip4(SNAT))
    dp.swap()
    return dp, up, ifs


def churn_batches(seed, n, up, ifs):
    """``n`` packed [5, B] batches drawn from a pool of 48 flows —
    inbound from the uplink, pod to pod, pod to the VIP, pod to the
    outside (SNAT), and their replies — so sessions are installed,
    hit, evicted and aged; a few lanes per batch are invalid."""
    rng = np.random.default_rng(seed)
    pool = []
    for k in range(48):
        kind = k % 4
        pod = int(rng.integers(len(PODS)))
        if kind == 0:    # inbound from outside
            f = (ip4(f"172.16.{k}.9"), ip4(PODS[pod]), up)
        elif kind == 1:  # pod to pod
            f = (ip4(PODS[pod]), ip4(PODS[(pod + 1) % len(PODS)]),
                 ifs[pod])
        elif kind == 2:  # pod to the VIP
            f = (ip4(PODS[pod]), ip4(VIP), ifs[pod])
        else:            # pod to the outside
            f = (ip4(PODS[pod]), ip4(f"8.8.{k}.8"), ifs[pod])
        proto = int(rng.choice([6, 6, 17, 1]))
        dport = 80 if kind == 2 else int(rng.choice([80, 8080, 53, 443]))
        pool.append((f, proto, 1024 + k, dport))
    out = []
    for _ in range(n):
        pick = rng.integers(len(pool), size=B)
        rev = rng.random(B) < 0.3
        cols = {c: np.zeros(B, np.int32) for c in
                ("proto", "sport", "dport", "ttl", "pkt_len", "rx_if",
                 "flags")}
        cols["src_ip"] = np.zeros(B, np.uint32)
        cols["dst_ip"] = np.zeros(B, np.uint32)
        for j, p in enumerate(pick):
            (src, dst, rx), proto, sport, dport = pool[p]
            if rev[j]:  # a reply: the tuple reversed, from the far side
                src, dst, sport, dport = dst, src, dport, sport
                rx = up if rx != up else ifs[0]
            cols["src_ip"][j], cols["dst_ip"][j] = src, dst
            cols["proto"][j], cols["sport"][j] = proto, sport
            cols["dport"][j], cols["rx_if"][j] = dport, rx
        cols["ttl"][:] = rng.integers(1, 65, B)
        cols["pkt_len"][:] = 64
        cols["flags"][:] = np.where(rng.random(B) < 0.9, FLAG_VALID, 0)
        flat = packed_input_zeros(B)
        pack_packet_columns(flat.view(np.uint32), cols, B)
        out.append(flat)
    return out


def assert_tables_equal(a: DataplaneTables, b: DataplaneTables, where):
    ha, hb = jax.device_get((a, b))
    for f, x, y in zip(DataplaneTables._fields, ha, hb):
        assert np.array_equal(np.asarray(x), np.asarray(y)), (where, f)


# (fast tier, telemetry, tenancy, chain K): 60 dispatches each
CASES = {
    "full": (False, "off", "off", 1),
    "two_tier": (True, "off", "off", 1),
    "tel_full": (True, "full", "off", 1),
    "tenancy": (True, "off", "on", 1),
    "chain4": (True, "off", "off", 4),
}


@pytest.mark.parametrize("case", list(CASES))
def test_written_only_step_is_bit_identical(case):
    """The written-only program and the all-leaf one, each threading
    its own tables over the same batches, give identical results, aux
    rows and all 174 table leaves."""
    fast, tel, tnt, k = CASES[case]
    dp, up, ifs = churn_dp(telemetry=tel, tenancy=tnt)
    fn = make_pipeline_step("dense", False, fast, 16, tel_mode=tel,
                            tnt_mode=tnt)
    if k == 1:
        new = jax.jit(_packed_call(fn, with_aux=True, tel=tel))
    else:
        new = jax.jit(_chained_call(fn, with_aux=True, tel=tel))
    ref_one = _packed_tables(fn, with_aux=True, tel=tel)

    def ref_chain(tables, flats, now, *stamps):
        # the chain as it was: the whole tables pytree as scan carry
        def body(tbl, xs):
            tbl, out, aux = ref_one(tbl, xs[0], now, *xs[1:], *stamps[1:])
            return tbl, (out, aux)

        xs = (flats,) + stamps[:1]
        tables, (outs, auxs) = jax.lax.scan(body, tables, xs)
        return tables, outs, auxs

    ref = jax.jit(ref_chain if k > 1 else ref_one)
    batches = churn_batches(list(CASES).index(case), 60 * k, up, ifs)
    t_new = t_ref = dp.tables
    now = 1
    hits = 0
    for i in range(60):
        now += int(i % 7 == 0) * 25 + 1  # a jump ages some sessions out
        flat = np.stack(batches[i * k:(i + 1) * k]) if k > 1 \
            else batches[i]
        args = (jnp.asarray(flat), jnp.int32(now))
        if tel != "off":
            stamp = (jnp.full((k,), now, jnp.int32) if k > 1
                     else jnp.int32(now))
            args += (stamp, jnp.int32(now + 7))
        written, out, aux = new(t_new, *args)
        t_ref, out_r, aux_r = ref(t_ref, *args)
        assert np.array_equal(np.asarray(out), np.asarray(out_r)), (case, i)
        assert np.array_equal(np.asarray(aux), np.asarray(aux_r)), (case, i)
        assert len(written) < len(DataplaneTables._fields)
        t_new = t_new._replace(**written)
        hits += int(np.asarray(aux).reshape(-1, aux.shape[-1])[:, 2].sum())
        if i % 20 == 19:
            assert_tables_equal(t_new, t_ref, (case, i))
    # the traffic did churn the session table
    assert hits > 0
    assert 0 < int(jnp.sum(t_new.sess_valid)) <= 64


def test_default_config_written_set():
    """For the default configuration the step writes exactly the ECMP
    counters, the session columns and the two sweep cursors, in both
    tiers; process_packed reports it in step_out and
    kernel_snapshot."""
    dp = Dataplane(DataplaneConfig(max_ifaces=128))
    args = (dp.tables, jnp.asarray(packed_input_zeros(VEC)), jnp.int32(1))
    for fast in (False, True):
        fn = make_pipeline_step("dense", True, fast, 256)
        written = jax.eval_shape(_packed_call(fn, with_aux=True), *args)[0]
        assert set(written) == DEFAULT_WRITES, fast
    dp.process_packed(packed_input_zeros(VEC))
    leaves, nbytes = dp.step_out
    assert leaves == len(DEFAULT_WRITES)
    assert nbytes == sum(
        getattr(dp.tables, f).nbytes for f in DEFAULT_WRITES)
    assert dp.kernel_snapshot()["step_out"] == {
        "leaves": leaves, "bytes": nbytes}


def test_commit_keeps_read_only_planes_and_drops_a_stale_result():
    """A committed step swaps in new session columns and keeps every
    read-only plane as the very buffer the caller's tables held; a
    result whose tables were replaced mid-call is discarded."""
    dp, up, ifs = churn_dp()
    flat = churn_batches(5, 1, up, ifs)[0]
    before = dp.tables
    dp.process_packed(flat, now=3)
    after = dp.tables
    assert after is not before
    for f, a, b in zip(DataplaneTables._fields, before, after):
        if f not in DEFAULT_WRITES:
            assert a is b, f
    assert int(jnp.sum(after.sess_valid)) > 0

    step = dp._get_step(dp._use_fastpath, "packed")
    swapped = []

    def racing(tables, *args):
        with dp._lock:  # an epoch lands while the step is in flight
            dp.tables = dp.builder.to_device(sessions=dp.tables)
            swapped.append(dp.tables)
        return step(tables, *args)

    dp._get_step = lambda fast, form="plain": racing
    dp.process_packed(flat, now=4)
    assert dp.tables is swapped[0]


def push_frames(rings, rx_if, n_frames, per, codec, scratch, stop):
    """Frame k tagged sport=20000+k; waits for rx slots as the pump
    frees them."""
    for k in range(n_frames):
        frames = [make_frame(PODS[0], PODS[1], proto=17, sport=20000 + k,
                             dport=1000 + j) for j in range(per)]
        cols, n = codec.parse(frames, rx_if, scratch)
        while not rings.rx.push(cols, n, payload=scratch):
            if stop.is_set():
                return
            time.sleep(0.001)


def test_pump_serves_while_tables_are_read_and_swapped():
    """DataplanePump serves frames while a second thread keeps reading
    ``dp.tables`` (the collector's session count) and one epoch swap
    lands mid-run: no batch fails, nothing is dropped, every frame
    comes back in order."""
    dp, _up, ifs = churn_dp()
    rings = IORingPair(n_slots=16)
    n_frames, per = 48, 8
    pump = DataplanePump(dp, rings, max_batch=VEC, fetch_workers=2,
                         max_inflight=4)
    pump.warm()
    stop = threading.Event()
    reads, errors = [0], []

    def reader():
        while not stop.is_set():
            try:
                with dp._lock:
                    t = dp.tables
                int(jnp.sum(t.sess_valid))
                reads[0] += 1
            except Exception as e:  # noqa: BLE001 — the test reports it
                errors.append(e)
                return

    codec = PacketCodec()
    scratch = np.zeros((VEC, rings.rx.snap), np.uint8)
    threads = [threading.Thread(target=reader, daemon=True),
               threading.Thread(target=push_frames, daemon=True,
                                args=(rings, ifs[0], n_frames, per, codec,
                                      scratch, stop))]
    pump.start()
    for th in threads:
        th.start()
    got = []
    try:
        deadline = time.monotonic() + 180
        while len(got) < n_frames and time.monotonic() < deadline:
            f = rings.tx.peek()
            if f is None:
                time.sleep(0.002)
                continue
            got.append((int(f.cols["sport"][0]), f.n,
                        int(f.cols["rx_if"][0])))
            rings.tx.release()
            if len(got) == n_frames // 2:
                with dp.commit_lock:  # one epoch swap mid-run
                    dp.builder.add_route("10.2.0.0/16", ifs[2],
                                         Disposition.LOCAL)
                    epoch = dp.swap()
    finally:
        stop.set()
        for th in threads:
            th.join(timeout=30)
        assert pump.stop()
        rings.close()
    assert not any(th.is_alive() for th in threads)
    assert errors == []
    assert reads[0] > 0
    assert epoch == 2
    assert [s for s, _n, _i in got] == [20000 + k for k in range(n_frames)]
    assert all(n == per and i == ifs[1] for _s, n, i in got)
    assert pump.stats["batch_errors"] == 0
    assert pump.stats["drops_error"] == 0
    assert pump.stats["frames"] == n_frames
    assert 0 < dp.step_out[0] < len(DataplaneTables._fields)
