"""Two-node e2e with real processes (VERDICT r2 Next #4).

The full control+data story of the reference's two_node_two_pods robot
suite (tests/robot/suites/two_node_two_pods.robot), with real process
boundaries everywhere the deployment has them:

  * the FENCED store trio as three subprocesses — quorum witness,
    primary kvserver, warm-standby kvserver (the chart's three
    Deployments; the etcd analog with its raft-quorum guarantee
    rebuilt as 2 replicas + arbiter, kvstore/witness.py) — agents'
    store_url lists both endpoints,
  * per node: a vpp-tpu-agent subprocess and a vpp-tpu-io subprocess
    (launched from the agent's published IO plan, exactly as
    vpp-tpu-init does),
  * a veth pair as the inter-node fabric: each node's IO daemon binds
    one leg as its uplink; node-to-node pod traffic rides VXLAN over it
    (node_events.go:184-250 analog routes installed via the shared
    store's node-liveness events),
  * netns "pods" wired by CNI Adds over each agent's unix socket.

Asserts: pod on node A reaches pod on node B (UDP through both device
pipelines + VXLAN encap/decap), a NetworkPolicy published through the
store (KSR key scheme) cuts that traffic off — and after the primary
store CRASHES mid-cluster, the witness-arbitrated failover promotes
the standby, a policy delete lands on the new primary (fenced write),
and cross-node traffic resumes with no agent restarts.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
import urllib.request

import pytest

from vpp_tpu.cni.transport import cni_call
from vpp_tpu.cni.wiring import host_ifname
from vpp_tpu.cmd.config import AgentConfig
from vpp_tpu.cmd.init_main import InitSupervisor
from vpp_tpu.ksr import model as m
from vpp_tpu.kvstore.client import RemoteKVStore


def _can_netns() -> bool:
    try:
        r = subprocess.run(["ip", "netns", "add", "vppt2selfck"],
                           capture_output=True, timeout=10)
        if r.returncode == 0:
            subprocess.run(["ip", "netns", "del", "vppt2selfck"],
                           capture_output=True, timeout=10)
            return True
        return False
    except (OSError, subprocess.TimeoutExpired):
        return False


pytestmark = pytest.mark.skipif(
    not _can_netns(), reason="needs CAP_NET_ADMIN (netns/veth)"
)

RUN = "/tmp/vppt2-run"
FAB = ("vppt2-faba", "vppt2-fabb")
PODS = {"a": "vppt2-poda", "b": "vppt2-podb"}
CIDS = {"a": "aa02" * 5, "b": "bb02" * 5}
KSR_PREFIX = "ksr/"


def sh(*a, **kw):
    return subprocess.run(list(a), capture_output=True, text=True, **kw)


def _cleanup():
    for ns in PODS.values():
        sh("ip", "netns", "del", ns)
    for cid in CIDS.values():
        sh("ip", "link", "del", host_ifname(cid))
    sh("ip", "link", "del", FAB[0])


def _child_env():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _wait_ready(port: int, timeout: float = 120.0) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/readiness", timeout=2
            ) as r:
                if r.status == 200:
                    return
        except Exception:
            pass
        time.sleep(0.5)
    raise TimeoutError(f"agent on :{port} never became ready")


class Node:
    def __init__(self, name: str, fab_if: str, kv_ports, ports):
        self.name = name
        self.dir = f"{RUN}/{name}"
        os.makedirs(self.dir, exist_ok=True)
        self.cni_socket = f"{self.dir}/cni.sock"
        self.health_port = ports[0]
        store_url = "tcp://" + ",".join(
            f"127.0.0.1:{p}" for p in kv_ports)
        cfg = {
            "node_name": name,
            "store_url": store_url,
            "cni_socket": self.cni_socket,
            "cli_socket": f"{self.dir}/cli.sock",
            "stats_port": ports[1],
            "health_port": ports[0],
            "http_host": "127.0.0.1",
            "io": {
                "enabled": True,
                "shm_name": f"vppt2-{name}",
                "n_slots": 32,
                "snap": 2048,
                "control_socket": f"{self.dir}/io-ctl.sock",
                "uplink_interface": fab_if,
                "plan_path": f"{self.dir}/io-plan.json",
            },
        }
        self.cfg_path = f"{self.dir}/contiv.yaml"
        with open(self.cfg_path, "w") as f:
            json.dump(cfg, f)   # YAML is a JSON superset
        self.agent = None
        self.io = None

    def start(self):
        env = _child_env()
        self._agent_log = open(f"{self.dir}/agent.log", "w")
        self.agent = subprocess.Popen(
            [sys.executable, "-m", "vpp_tpu.cmd.agent",
             "--config", self.cfg_path],
            env=env, stdout=self._agent_log, stderr=subprocess.STDOUT,
        )
        # launch the IO daemon exactly as vpp-tpu-init would
        sup = InitSupervisor(
            AgentConfig.from_dict(json.load(open(self.cfg_path))),
            self.cfg_path, plan_timeout_s=120.0,
        )
        (_, plan), = sup.read_plans().values()
        self._io_log = open(f"{self.dir}/io.log", "w")
        self.io = subprocess.Popen(
            sup.io_argv(plan), env=env,
            stdout=self._io_log, stderr=subprocess.STDOUT,
        )
        from vpp_tpu.io.control import IOControlClient

        ctl = IOControlClient(plan["control_socket"], timeout=3.0)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and not ctl.ping():
            assert self.io.poll() is None, "io daemon died during startup"
            time.sleep(0.5)
        return self

    def add_pod(self, cid: str, ns: str, pod_name: str) -> str:
        # kubelet-style retry loop: TRY_AGAIN (11) while the vswitch
        # base config / IO daemon comes up
        deadline = time.monotonic() + 90
        while True:
            reply = cni_call(self.cni_socket, "Add", {
                "container_id": cid, "netns": f"/var/run/netns/{ns}",
                "if_name": "eth0",
                "extra_args": {"K8S_POD_NAME": pod_name,
                               "K8S_POD_NAMESPACE": "default"},
            }, timeout=60.0)
            if reply["result"] == 11 and time.monotonic() < deadline:
                time.sleep(1.0)
                continue
            assert reply["result"] == 0, reply
            return reply["interfaces"][0]["ip_addresses"][0][
                "address"].split("/")[0]

    def stop(self):
        for p in (self.io, self.agent):
            if p is not None and p.poll() is None:
                p.terminate()
        for p in (self.io, self.agent):
            if p is not None:
                try:
                    p.wait(timeout=15)
                except subprocess.TimeoutExpired:
                    p.kill()


@pytest.fixture(scope="module")
def cluster():
    import shutil

    _cleanup()
    shutil.rmtree(RUN, ignore_errors=True)  # stale plans/sockets poison
    os.makedirs(RUN, exist_ok=True)         # the boot handshake

    for ns in PODS.values():
        subprocess.run(["ip", "netns", "add", ns], check=True, timeout=10)
    # the inter-node fabric
    subprocess.run(["ip", "link", "add", FAB[0], "type", "veth",
                    "peer", "name", FAB[1]], check=True, timeout=10)
    for f in FAB:
        subprocess.run(["ip", "link", "set", f, "up"], check=True,
                       timeout=10)

    env = _child_env()

    def _port(path, timeout=30):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            try:
                return int(open(path).read())
            except (OSError, ValueError):
                time.sleep(0.2)
        raise TimeoutError(path)

    # the fenced store trio, wired exactly as the chart deploys it.
    # fence-ttl is generous for the 1-core host: thread starvation
    # under load must not read as a dead primary mid-test.
    witness = subprocess.Popen(
        [sys.executable, "-m", "vpp_tpu.cmd.kvwitness", "--host",
         "127.0.0.1", "--port", "0", "--port-file", f"{RUN}/w.port"],
        env=env)
    w_port = _port(f"{RUN}/w.port")
    kv = subprocess.Popen(
        [sys.executable, "-m", "vpp_tpu.cmd.kvserver", "--host",
         "127.0.0.1", "--port", "0", "--port-file", f"{RUN}/kv.port",
         "--witness", f"127.0.0.1:{w_port}", "--fence-ttl", "6"],
        env=env)
    kv_port = _port(f"{RUN}/kv.port")
    standby = subprocess.Popen(
        [sys.executable, "-m", "vpp_tpu.cmd.kvserver", "--host",
         "127.0.0.1", "--port", "0", "--port-file", f"{RUN}/sb.port",
         "--follow", f"127.0.0.1:{kv_port}",
         "--witness", f"127.0.0.1:{w_port}",
         "--fence-ttl", "6", "--promote-after", "3"],
        env=env)
    sb_port = _port(f"{RUN}/sb.port", timeout=60)

    node_a = Node("node-a", FAB[0], (kv_port, sb_port),
                  (21191, 21991)).start()
    node_b = Node("node-b", FAB[1], (kv_port, sb_port),
                  (21192, 21992)).start()
    try:
        _wait_ready(node_a.health_port)
        _wait_ready(node_b.health_port)
        yield {"a": node_a, "b": node_b, "kv_port": kv_port,
               "sb_port": sb_port, "w_port": w_port,
               "kv_proc": kv}
    finally:
        for n in (node_a, node_b):
            try:
                n.stop()
            except Exception:
                pass
        for p in (standby, kv, witness):
            if p.poll() is None:
                p.terminate()
        for p in (standby, kv, witness):
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        _cleanup()


def _udp_recv(ns: str, port: int, timeout_s: int = 60):
    return subprocess.Popen(
        ["ip", "netns", "exec", ns, sys.executable, "-c",
         "import socket\ns=socket.socket(socket.AF_INET,socket.SOCK_DGRAM)\n"
         f"s.bind(('0.0.0.0', {port}))\ns.settimeout({timeout_s})\n"
         "d,p=s.recvfrom(4096)\nprint(d.decode()+'|'+p[0], flush=True)\n"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )


def _udp_spray(ns: str, dst: str, port: int, msg: str, times: int,
               gap: float = 0.25):
    subprocess.run(
        ["ip", "netns", "exec", ns, sys.executable, "-c",
         "import socket,time\n"
         "s=socket.socket(socket.AF_INET,socket.SOCK_DGRAM)\n"
         f"for _ in range({times}):\n"
         f"    s.sendto({msg!r}.encode(), ('{dst}', {port}))\n"
         f"    time.sleep({gap})\n"],
        timeout=times * gap + 30, capture_output=True, check=True,
    )


# slow: ~2 min of subprocess boots, netns traffic and failover polling
# — the single largest tier-1 line item, and the two cases are a
# SEQUENCE (the failover case un-blocks the policy the first case
# cut), so they move to the slow tier together. The mesh suite now
# RUNS on this toolchain (ISSUE 12 un-skipped ~20 tests), and the
# `-m 'not slow'` budget can't absorb both; fenced-store failover
# stays covered in tier-1 by test_kvstore_fencing, cross-node wire by
# test_mesh_wire_e2e/test_proxy_chain_e2e.
@pytest.mark.slow
class TestTwoNodeTwoPods:
    def test_cross_node_udp_then_policy_cutoff(self, cluster):
        a, b = cluster["a"], cluster["b"]
        ip_a = a.add_pod(CIDS["a"], PODS["a"], "pod-a")
        ip_b = b.add_pod(CIDS["b"], PODS["b"], "pod-b")
        # different nodes -> different /24s of the pod supernet
        assert ip_a.split(".")[2] != ip_b.split(".")[2]

        # pod A (node A) -> pod B (node B): crosses both pipelines and
        # the VXLAN fabric. Generous spray: first packets pay each
        # side's jit compile.
        recv = _udp_recv(PODS["b"], 6011, timeout_s=110)
        time.sleep(0.5)
        _udp_spray(PODS["a"], ip_b, 6011, "cross-node-hello", times=400)
        out, err = recv.communicate(timeout=120)
        assert "cross-node-hello" in out, (out, err)
        assert ip_a in out

        # ClusterIP service leg (robot suite's service case): a VIP on
        # UDP/5300 backed by pod-b on the OTHER node. Pod A sends to
        # the VIP; node A's NAT44 DNATs to pod-b and the flow rides the
        # VXLAN fabric to node B.
        cli = RemoteKVStore("127.0.0.1", cluster["kv_port"])
        try:
            svc = m.Service(
                name="svc-b", namespace="default",
                cluster_ip="10.96.0.50",
                ports=[m.ServicePort(name="u", protocol="UDP", port=5300,
                                     target_port=6013)],
                selector={"app": "b"},
            )
            eps = m.Endpoints(
                name="svc-b", namespace="default",
                subsets=[m.EndpointSubset(
                    addresses=[m.EndpointAddress(
                        ip=ip_b, node_name="node-b",
                        target_pod="default/pod-b")],
                    ports=[m.EndpointPort(name="u", port=6013,
                                          protocol="UDP")],
                )],
            )
            cli.put(KSR_PREFIX + svc.key(), svc.to_dict())
            cli.put(KSR_PREFIX + eps.key(), eps.to_dict())
            deadline = time.monotonic() + 60
            got_vip = False
            while time.monotonic() < deadline and not got_vip:
                recv_svc = _udp_recv(PODS["b"], 6013, timeout_s=8)
                time.sleep(0.3)
                try:
                    _udp_spray(PODS["a"], "10.96.0.50", 5300,
                               "via-the-vip", times=16)
                except subprocess.CalledProcessError:
                    pass
                out_svc, _ = recv_svc.communicate(timeout=30)
                got_vip = "via-the-vip" in (out_svc or "")
            assert got_vip, "ClusterIP DNAT across nodes never delivered"

            # NetworkPolicy via the store (KSR key scheme): pod-b accepts
            # only TCP/9 -> the UDP flows must die in node B's classifier
            pod_a = m.Pod(name="pod-a", namespace="default",
                          labels={"app": "a"}, ip_address=ip_a)
            pod_b = m.Pod(name="pod-b", namespace="default",
                          labels={"app": "b"}, ip_address=ip_b)
            cli.put(KSR_PREFIX + pod_a.key(), pod_a.to_dict())
            cli.put(KSR_PREFIX + pod_b.key(), pod_b.to_dict())
            pol = m.Policy(
                name="lock-b", namespace="default",
                pods=m.LabelSelector(match_labels={"app": "b"}),
                policy_type=m.POLICY_INGRESS,
                ingress_rules=[m.PolicyRule(
                    ports=[m.PolicyPort(protocol="TCP", port=9)],
                    peers=[],
                )],
            )
            cli.put(KSR_PREFIX + pol.key(), pol.to_dict())

            # wait for the render to land, then verify the cutoff
            deadline = time.monotonic() + 60
            blocked = False
            while time.monotonic() < deadline and not blocked:
                recv2 = _udp_recv(PODS["b"], 6012, timeout_s=6)
                time.sleep(0.3)
                try:
                    _udp_spray(PODS["a"], ip_b, 6012, "blocked?", times=12)
                except subprocess.CalledProcessError:
                    pass
                out2, _ = recv2.communicate(timeout=30)
                blocked = "blocked?" not in (out2 or "")
            assert blocked, "policy never cut cross-node traffic"
        finally:
            cli.close()

    def test_store_failover_keeps_cluster_serving(self, cluster):
        """The primary store CRASHES under the live cluster (the
        etcd-pod-death case the reference rides Kubernetes restarts
        for): the witness grants the standby's claim, both agents fail
        over (watch resync, fenced writes at the bumped epoch), a
        policy DELETE through the new primary un-blocks the cross-node
        traffic the previous test cut — the whole control loop keeps
        working with no agent or daemon restarts."""
        import signal

        from vpp_tpu.kvstore.witness import WitnessClient

        cluster["kv_proc"].send_signal(signal.SIGKILL)
        cluster["kv_proc"].wait(timeout=15)

        # witness-arbitrated promotion: the standby is the new primary
        wc = WitnessClient(f"127.0.0.1:{cluster['w_port']}")
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            st = wc.status()
            if st["primary"] == f"127.0.0.1:{cluster['sb_port']}" \
                    and st["epoch"] >= 1:
                break
            time.sleep(0.5)
        else:
            raise AssertionError(f"standby never promoted: {wc.status()}")

        # a fenced write through the failed-over client: deleting the
        # lock-b policy must re-open pod-b (renderer unwind on BOTH
        # nodes, driven entirely by the new primary's watch stream)
        cli = RemoteKVStore(
            "127.0.0.1", cluster["kv_port"], request_timeout=20.0,
            reconnect_timeout=30.0,
            fallbacks=[("127.0.0.1", cluster["sb_port"])])
        try:
            pol_key = KSR_PREFIX + m.Policy(
                name="lock-b", namespace="default").key()
            assert cli.get(pol_key) is not None, \
                "expected the previous test's policy in the store"
            assert cli.delete(pol_key) is True
            assert cli.fencing_epoch >= 1

            # pod-b's IP from the store (survived the failover via
            # replication)
            pod_b = cli.get(KSR_PREFIX + m.Pod(
                name="pod-b", namespace="default").key())
            ip_b = pod_b["ip_address"]

            deadline = time.monotonic() + 90
            flowing = False
            while time.monotonic() < deadline and not flowing:
                recv3 = _udp_recv(PODS["b"], 6014, timeout_s=6)
                time.sleep(0.3)
                try:
                    _udp_spray(PODS["a"], ip_b, 6014,
                               "after-failover", times=12)
                except subprocess.CalledProcessError:
                    pass
                out3, _ = recv3.communicate(timeout=30)
                flowing = "after-failover" in (out3 or "")
            assert flowing, (
                "cross-node traffic never resumed after the store "
                "failover + policy delete")
        finally:
            cli.close()
