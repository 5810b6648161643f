"""Statscollector + Prometheus exposition tests.

Reference model: plugins/statscollector/plugin_statscollector_test.go
(mockPrometheus + mockContiv injection → assert gauge values and pod
labels) and the KSR gauge surface (ksr_statscollector.go).
"""

import urllib.request


from vpp_tpu.cni import ContainerIndex, RemoteCNIServer
from vpp_tpu.cni.model import CNIRequest
from vpp_tpu.ipam.ipam import IPAM
from vpp_tpu.ksr.reflector import ReflectorRegistry, Reflector, MockK8sListWatch
from vpp_tpu.kvstore.store import Broker, KVStore
from vpp_tpu.pipeline.dataplane import Dataplane
from vpp_tpu.pipeline.tables import DataplaneConfig
from vpp_tpu.pipeline.vector import make_packet_vector
from vpp_tpu.stats import Gauge, MetricsRegistry, StatsCollector, StatsHTTPServer
from vpp_tpu.stats.collector import STATS_PATH, register_ksr_gauges


def wired_node():
    dp = Dataplane(DataplaneConfig(sess_slots=256))
    dp.add_uplink()
    dp.add_host_interface()
    ipam = IPAM(node_id=1)
    index = ContainerIndex()
    srv = RemoteCNIServer(dp, ipam, index)
    srv.set_ready()
    r1 = srv.add(CNIRequest(container_id="c1", extra_args={
        "K8S_POD_NAME": "web", "K8S_POD_NAMESPACE": "prod"}))
    r2 = srv.add(CNIRequest(container_id="c2", extra_args={
        "K8S_POD_NAME": "db", "K8S_POD_NAMESPACE": "prod"}))
    ip1 = r1.interfaces[0].ip_addresses[0].address.split("/")[0]
    ip2 = r2.interfaces[0].ip_addresses[0].address.split("/")[0]
    return dp, index, srv, ip1, ip2


def test_collector_pod_labels_and_counts():
    dp, index, srv, ip1, ip2 = wired_node()
    coll = StatsCollector(dp, index)
    if1 = dp.pod_if[("prod", "web")]
    res = dp.process(make_packet_vector(
        [dict(src=ip1, dst=ip2, proto=6, sport=1000 + i, dport=80,
              len=100, rx_if=if1) for i in range(5)]
    ))
    assert int(res.stats.tx) == 5
    coll.update(res.stats)
    coll.publish()

    g_in = coll.if_gauges["vpp_tpu_if_in_packets"]
    g_out = coll.if_gauges["vpp_tpu_if_out_packets"]
    g_bytes = coll.if_gauges["vpp_tpu_if_in_bytes"]
    web = dict(podName="web", podNamespace="prod", interfaceName="eth0")
    db = dict(podName="db", podNamespace="prod", interfaceName="eth0")
    assert g_in.get(**web) == 5
    assert g_bytes.get(**web) == 500
    assert g_out.get(**db) == 5
    assert coll.node_gauges["vpp_tpu_node_rx_packets"].get() == 5
    assert coll.node_gauges["vpp_tpu_node_tx_packets"].get() == 5
    # accumulation across frames
    res2 = dp.process(make_packet_vector(
        [dict(src=ip1, dst=ip2, proto=6, sport=2000, dport=80,
              len=100, rx_if=if1)]
    ))
    coll.update(res2.stats)
    coll.publish()
    assert g_in.get(**web) == 6


def test_collector_drop_attribution():
    dp, index, srv, ip1, ip2 = wired_node()
    coll = StatsCollector(dp, index)
    if1 = dp.pod_if[("prod", "web")]
    res = dp.process(make_packet_vector(
        [dict(src=ip1, dst="203.0.113.9", proto=6, sport=1, dport=2,
              rx_if=if1)]  # no route
    ))
    coll.update(res.stats)
    coll.publish()
    web = dict(podName="web", podNamespace="prod", interfaceName="eth0")
    assert coll.if_gauges["vpp_tpu_if_drop_packets"].get(**web) == 1
    assert coll.node_gauges["vpp_tpu_node_drop_no_route"].get() == 1


def test_deleted_pod_gauges_removed():
    dp, index, srv, ip1, ip2 = wired_node()
    coll = StatsCollector(dp, index)
    if1 = dp.pod_if[("prod", "web")]
    res = dp.process(make_packet_vector(
        [dict(src=ip1, dst=ip2, proto=6, sport=1, dport=80, rx_if=if1)]
    ))
    coll.update(res.stats)
    coll.publish()
    web = dict(podName="web", podNamespace="prod", interfaceName="eth0")
    assert coll.if_gauges["vpp_tpu_if_in_packets"].get(**web) == 1

    srv.delete(CNIRequest(container_id="c1"))
    coll.publish()
    assert coll.if_gauges["vpp_tpu_if_in_packets"].get(**web) == 0


def test_http_exposition_roundtrip():
    dp, index, srv, ip1, ip2 = wired_node()
    coll = StatsCollector(dp, index)
    if1 = dp.pod_if[("prod", "web")]
    res = dp.process(make_packet_vector(
        [dict(src=ip1, dst=ip2, proto=6, sport=1, dport=80, rx_if=if1)]
    ))
    coll.update(res.stats)
    coll.publish()
    server = StatsHTTPServer(coll.registry, port=0)
    server.start()
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}{STATS_PATH}", timeout=10
        ).read().decode()
        assert 'vpp_tpu_if_in_packets{interfaceName="eth0",podName="web",podNamespace="prod"} 1' in body
        assert "# TYPE vpp_tpu_node_rx_packets gauge" in body
        # unknown path → 404
        try:
            urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/nope", timeout=10
            )
            assert False, "expected 404"
        except urllib.error.HTTPError as e:
            assert e.code == 404
    finally:
        server.close()


def test_ksr_gauges():
    store = KVStore()
    watch = MockK8sListWatch()
    registry = ReflectorRegistry()

    class Obj:
        def __init__(self, name):
            self.name = name

        def key(self):
            return f"k8s/pod/{self.name}"

        def to_dict(self):
            return {"name": self.name}

    refl = Reflector(
        obj_type="pod",
        broker=Broker(store, "ksr/"),
        list_watch=watch,
        converter=lambda o: Obj(o["name"]),
    )
    registry.add(refl)
    refl.start()
    watch.add("p1", {"name": "p1"})
    watch.add("p2", {"name": "p2"})
    watch.delete("p1")

    mreg = MetricsRegistry()
    gauges, publish_ksr = register_ksr_gauges(mreg, registry)
    publish_ksr()
    assert gauges["adds"].get(reflector="pod") == 2
    assert gauges["deletes"].get(reflector="pod") == 1
    body = mreg.render("/metrics")
    assert 'vpp_tpu_ksr_adds{reflector="pod"} 2' in body


def test_reused_interface_slot_starts_at_zero():
    dp, index, srv, ip1, ip2 = wired_node()
    coll = StatsCollector(dp, index)
    if1 = dp.pod_if[("prod", "web")]
    res = dp.process(make_packet_vector(
        [dict(src=ip1, dst=ip2, proto=6, sport=1, dport=80, rx_if=if1)]
    ))
    coll.update(res.stats)
    srv.delete(CNIRequest(container_id="c1"))
    # new pod reuses the freed slot (LIFO allocator)
    srv.add(CNIRequest(container_id="c3", extra_args={
        "K8S_POD_NAME": "api", "K8S_POD_NAMESPACE": "prod"}))
    assert dp.pod_if[("prod", "api")] == if1
    coll.publish()
    api = dict(podName="api", podNamespace="prod", interfaceName="eth0")
    assert coll.if_gauges["vpp_tpu_if_in_packets"].get(**api) == 0


def test_gauge_large_values_exact():
    g = Gauge("big")
    g.set(12345678)
    assert "big 12345678" in g.render()
    g2 = Gauge("frac")
    g2.set(0.25)
    assert "frac 0.25" in g2.render()


def test_http_path_with_query_string():
    reg = MetricsRegistry()
    reg.register(STATS_PATH, Gauge("x")).set(1)
    server = StatsHTTPServer(reg, port=0)
    server.start()
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}{STATS_PATH}?ts=123", timeout=10
        ).read().decode()
        assert "x 1" in body
    finally:
        server.close()


def test_gauge_render_escaping():
    g = Gauge("x", "help")
    g.set(1, name='we"ird\\pod')
    lines = g.render()
    assert 'x{name="we\\"ird\\\\pod"} 1' in lines


def test_pump_counters_exported_over_prometheus():
    """IO pump counters (single-node or cluster pump — same stats
    contract) reach the Prometheus text exposition via set_pump()."""
    from vpp_tpu.pipeline.dataplane import Dataplane
    from vpp_tpu.pipeline.tables import DataplaneConfig
    from vpp_tpu.stats.collector import StatsCollector

    class FakePump:
        stats = {"frames": 7, "pkts": 1792, "batches": 3,
                 "tx_ring_full": 1, "batch_errors": 0,
                 "icmp_errors": 2, "fabric_pkts": 512,
                 "inflight": 5, "inflight_peak": 8,
                 "chain_batches": 4, "chain_k_peak": 2,
                 "t_pack": 0.25, "t_dispatch": 1.5,
                 "t_fetch_wait": 12.75, "t_fetch": 0.5, "t_write": 2.0,
                 "t_fetch_queue": 0.125, "t_reorder_wait": 0.375,
                 "t_dp_upload": 0.0625, "t_dp_call": 1.25,
                 "t_dispatch_cpu": 0.75,
                 "drops_tx_stall": 9, "drops_shutdown": 3,
                 "drops_rx_full": 0, "drops_error": 2,
                 "ring_windows": 6, "ring_frames": 11,
                 "ring_inflight": 1, "ring_lag": 2, "io_callbacks": 0,
                 "ml_scored": 1500, "ml_flagged": 42, "ml_drops": 17}

        @staticmethod
        def latency_us():
            return {"p50": 123.0, "p99": 456.0, "n": 3}

    dp = Dataplane(DataplaneConfig(
        max_tables=2, max_rules=8, max_global_rules=8, max_ifaces=8,
        fib_slots=16, sess_slots=64, nat_mappings=2, nat_backends=4))
    coll = StatsCollector(dp)
    coll.set_pump(FakePump())
    coll.publish()
    text = coll.registry.render("/stats")
    assert "vpp_tpu_pump_packets 1792" in text
    assert "vpp_tpu_pump_fabric_packets 512" in text
    assert "vpp_tpu_pump_icmp_errors 2" in text
    assert "vpp_tpu_pump_batch_latency_p99_us 456" in text
    # overlapped fetch ladder observability (ISSUE 1): the in-flight
    # window and the adaptive chainer's activity are exported...
    assert "vpp_tpu_pump_inflight_depth 5" in text
    assert "vpp_tpu_pump_inflight_peak 8" in text
    assert "vpp_tpu_pump_chained_dispatches 4" in text
    assert "vpp_tpu_pump_chain_k_peak 2" in text
    # ...and the per-stage cumulative seconds go out as one labelled
    # COUNTER family (so rate() gives per-second stage occupancy)
    assert "# TYPE vpp_tpu_pump_stage_seconds counter" in text
    assert 'vpp_tpu_pump_stage_seconds{stage="pack"} 0.25' in text
    assert 'vpp_tpu_pump_stage_seconds{stage="fetch_wait"} 12.75' in text
    assert 'vpp_tpu_pump_stage_seconds{stage="fetch"} 0.5' in text
    assert 'vpp_tpu_pump_stage_seconds{stage="write"} 2' in text
    # the dispatch call split and the waits between stages
    assert 'vpp_tpu_pump_stage_seconds{stage="fetch_queue"} 0.125' in text
    assert 'vpp_tpu_pump_stage_seconds{stage="reorder_wait"} 0.375' in text
    assert 'vpp_tpu_pump_stage_seconds{stage="dp_upload"} 0.0625' in text
    assert 'vpp_tpu_pump_stage_seconds{stage="dp_call"} 1.25' in text
    assert 'vpp_tpu_pump_stage_seconds{stage="dispatch_cpu"} 0.75' in text
    # device-ring telemetry + drop-cause attribution (ISSUE 7): the
    # io_callback-free steady state and the r5 goodput loss split are
    # exported, not inferred
    assert "vpp_tpu_pump_ring_windows 6" in text
    assert "vpp_tpu_pump_ring_frames 11" in text
    assert "vpp_tpu_pump_ring_inflight 1" in text
    assert "vpp_tpu_pump_ring_writeback_lag 2" in text
    assert "vpp_tpu_pump_io_callbacks 0" in text
    assert "# TYPE vpp_tpu_pump_drops_total counter" in text
    assert 'vpp_tpu_pump_drops_total{reason="tx_stall"} 9' in text
    assert 'vpp_tpu_pump_drops_total{reason="shutdown"} 3' in text
    assert 'vpp_tpu_pump_drops_total{reason="rx_full"} 0' in text
    assert 'vpp_tpu_pump_drops_total{reason="error"} 2' in text
    # ML-stage aux riders (ISSUE 10): the pump-side verdict counters
    assert "vpp_tpu_ml_pump_scored 1500" in text
    assert "vpp_tpu_ml_pump_flagged 42" in text
    assert "vpp_tpu_ml_pump_drops 17" in text


def test_pump_drops_rx_full_merges_daemon_stats():
    """The rx_full drop cause is counted where it happens — the IO
    daemon's rx thread — and folded into the same
    vpp_tpu_pump_drops_total family via set_io_daemon()."""
    from vpp_tpu.pipeline.dataplane import Dataplane
    from vpp_tpu.pipeline.tables import DataplaneConfig
    from vpp_tpu.stats.collector import StatsCollector

    class FakePump:
        stats = {"drops_rx_full": 0, "drops_tx_stall": 1,
                 "drops_shutdown": 0}

        @staticmethod
        def latency_us():
            return {"p50": 0.0, "p99": 0.0, "n": 0}

    dp = Dataplane(DataplaneConfig(
        max_tables=2, max_rules=8, max_global_rules=8, max_ifaces=8,
        fib_slots=16, sess_slots=64, nat_mappings=2, nat_backends=4))
    coll = StatsCollector(dp)
    coll.set_pump(FakePump())
    coll.set_io_daemon(lambda: {"drops_rx_full": 41})
    coll.publish()
    text = coll.registry.render("/stats")
    assert 'vpp_tpu_pump_drops_total{reason="rx_full"} 41' in text
    assert 'vpp_tpu_pump_drops_total{reason="tx_stall"} 1' in text
    # mesh mode: set_io_daemon WITHOUT set_pump (the pump is attached
    # to one designated collector cluster-wide) — daemon rx overflow
    # must still export, not be fetched and discarded
    coll2 = StatsCollector(dp, registry=None)
    coll2.set_io_daemon(lambda: {"drops_rx_full": 7})
    coll2.publish()
    text2 = coll2.registry.render("/stats")
    assert 'vpp_tpu_pump_drops_total{reason="rx_full"} 7' in text2


def test_ml_stage_families_exported():
    """Per-packet ML stage (ISSUE 10): StepStats verdict counters,
    the mode/version info gauges, the load ledger and the ml degraded
    component all reach the exposition."""
    import numpy as np

    from vpp_tpu.ir.rule import Action, ContivRule, Protocol
    from vpp_tpu.ml.model import MlModel
    from vpp_tpu.ops.mlscore import ML_FEATURES
    from vpp_tpu.pipeline.tables import DataplaneConfig
    from vpp_tpu.pipeline.vector import Disposition

    w1 = np.zeros((ML_FEATURES, 4), np.int8)
    w1[12, 0] = 1  # score == proto byte
    model = MlModel(
        kind="mlp", version=7, n_features=ML_FEATURES, w1=w1,
        b1=np.zeros(4, np.int32), s1=0,
        w2=np.array([1, 0, 0, 0], np.int8), b2=0,
        flag_thresh=10, action="drop").validate()
    dp = Dataplane(DataplaneConfig(
        max_tables=2, max_rules=8, max_global_rules=8, max_ifaces=8,
        fib_slots=16, sess_slots=64, nat_mappings=2, nat_backends=4,
        ml_stage="enforce", ml_hidden=4))
    uplink = dp.add_uplink()
    dp.builder.add_route("0.0.0.0/0", uplink, Disposition.REMOTE)
    dp.builder.set_global_table(
        [ContivRule(action=Action.PERMIT, protocol=Protocol.ANY)])
    dp.builder.set_ml_model(model)
    dp.swap()
    coll = StatsCollector(dp)
    res = dp.process(make_packet_vector(
        [dict(src="198.18.0.1", dst="203.0.113.9", proto=17, sport=53,
              dport=9000, rx_if=uplink),
         dict(src="198.18.0.2", dst="203.0.113.9", proto=6, sport=443,
              dport=9001, rx_if=uplink)]))
    coll.update(res.stats)

    class FailingSource:
        degraded = True

        @staticmethod
        def stats_snapshot():
            return {"outcomes": {"loaded": 1, "corrupt": 2},
                    "degraded": True, "last_error": "x",
                    "loaded_version": 7, "loaded_kind": "mlp",
                    "path": "/m.json"}

    coll.set_ml(FailingSource())
    coll.publish()
    text = coll.registry.render("/stats")
    assert "vpp_tpu_ml_scored_packets 2" in text
    assert "vpp_tpu_ml_flagged_packets 1" in text      # UDP flagged
    assert "vpp_tpu_ml_dropped_packets 1" in text      # and dropped
    assert 'vpp_tpu_ml_stage{mode="enforce"} 1' in text
    assert 'vpp_tpu_ml_stage{mode="off"} 0' in text
    assert "vpp_tpu_ml_model_version 7" in text
    assert 'vpp_tpu_ml_load_total{outcome="corrupt"} 2' in text
    assert 'vpp_tpu_degraded{component="ml"} 1' in text


def test_ml_degraded_defaults_healthy_without_source():
    """The ml degraded component always exports (0 = healthy) even
    with no loader attached — series absence is a wiring bug."""
    dp = Dataplane(DataplaneConfig(
        max_tables=2, max_rules=8, max_global_rules=8, max_ifaces=8,
        fib_slots=16, sess_slots=64, nat_mappings=2, nat_backends=4))
    coll = StatsCollector(dp)
    coll.publish()
    text = coll.registry.render("/stats")
    assert 'vpp_tpu_degraded{component="ml"} 0' in text
    assert 'vpp_tpu_ml_stage{mode="off"} 1' in text
    assert "vpp_tpu_ml_model_version 0" in text


def test_pump_stage_gauges_absent_keys_degrade_to_zero():
    """A pump without the ladder stats (the cluster pump predates some
    keys; a remote daemon may be an older build) must publish zeros,
    not crash the scrape path."""
    from vpp_tpu.pipeline.dataplane import Dataplane
    from vpp_tpu.pipeline.tables import DataplaneConfig
    from vpp_tpu.stats.collector import StatsCollector

    class BarePump:
        stats = {"frames": 1, "pkts": 2, "batches": 1}

        @staticmethod
        def latency_us():
            return {"p50": 0.0, "p99": 0.0, "n": 0}

    dp = Dataplane(DataplaneConfig(
        max_tables=2, max_rules=8, max_global_rules=8, max_ifaces=8,
        fib_slots=16, sess_slots=64, nat_mappings=2, nat_backends=4))
    coll = StatsCollector(dp)
    coll.set_pump(BarePump())
    coll.publish()
    text = coll.registry.render("/stats")
    assert "vpp_tpu_pump_inflight_depth 0" in text
    assert 'vpp_tpu_pump_stage_seconds{stage="dispatch"} 0' in text
