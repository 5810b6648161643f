"""vpp-tpu-init bootstrap: sequencing, supervision, uplink pre-config
(VERDICT r2 Next #5; reference cmd/contiv-init/main.go:201-273 +
vppcfg.go:74-559). Driven entirely against fakes — no root, no real
processes."""

from __future__ import annotations

import json
import subprocess
import sys
import threading
import time

import pytest

from vpp_tpu.cmd.config import AgentConfig, IOConfig
from vpp_tpu.cmd.init_main import InitSupervisor, configure_uplink


class FakeProc:
    def __init__(self, argv):
        self.argv = argv
        self.returncode = None
        self.terminated = False

    def poll(self):
        return self.returncode

    def terminate(self):
        self.terminated = True
        self.returncode = 0

    def wait(self, timeout=None):
        return self.returncode

    def kill(self):
        self.returncode = -9

    def die(self, rc=1):
        self.returncode = rc


class FakeSpawner:
    """Mimics the real children: spawning the "agent" writes the IO
    plan file (the handshake the real agent performs once its shm rings
    exist), unless plan_on_agent=False."""

    def __init__(self, cfg=None, plan_on_agent=True):
        self.cfg = cfg
        self.plan_on_agent = plan_on_agent
        self.spawned = []

    def __call__(self, argv):
        p = FakeProc(argv)
        self.spawned.append(p)
        if (self.cfg is not None and self.plan_on_agent
                and "vpp_tpu.cmd.agent" in argv):
            write_plan(self.cfg)
        if (self.cfg is not None and self.plan_on_agent
                and "vpp_tpu.cmd.mesh_main" in argv):
            # a mesh agent writes one plan per node (suffixed paths)
            for i in range(self.cfg.mesh.nodes):
                write_plan(self.cfg, _suffix=f".{i}",
                           shm=f"vpp-shm.{i}",
                           control_socket=f"/run/vpp-tpu/io-ctl.sock.{i}")
        return p

    def by_module(self, module):
        return [p for p in self.spawned if module in p.argv]


def cfg_with_io(tmp_path, **kw):
    return AgentConfig(
        node_name="n1",
        io=IOConfig(
            enabled=True, shm_name="vpp-shm", n_slots=32, snap=1024,
            control_socket="/run/vpp-tpu/io-ctl.sock",
            uplink_interface="eth9",
            plan_path=str(tmp_path / "io-plan.json"),
            **kw,
        ),
    )


def write_plan(cfg, _suffix="", **over):
    plan = {
        "shm": "vpp-shm", "slots": 32, "snap": 1024, "uplink_if": 63,
        "host_if": 62, "uplink_interface": "eth9",
        "vtep": 0xC0A81E01, "vni": 10,
        "control_socket": "/run/vpp-tpu/io-ctl.sock",
    }
    plan.update(over)
    with open(cfg.io.plan_path + _suffix, "w") as f:
        json.dump(plan, f)
    return plan


class TestBootSequence:
    def test_agent_then_plan_then_io(self, tmp_path):
        cfg = cfg_with_io(tmp_path)
        spawner = FakeSpawner(cfg)
        sup = InitSupervisor(cfg, "/etc/vpp-tpu/contiv.yaml",
                             spawn=spawner, plan_timeout_s=5.0)
        sup.start()
        agent_argv, io_argv = (spawner.spawned[0].argv,
                               spawner.spawned[1].argv)
        assert "vpp_tpu.cmd.agent" in agent_argv
        assert "--config" in agent_argv
        assert "vpp_tpu.cmd.io_daemon" in io_argv
        # geometry + endpoints come from the agent's plan, not guesses
        assert io_argv[io_argv.index("--shm") + 1] == "vpp-shm"
        assert io_argv[io_argv.index("--uplink") + 1] == "63"
        assert io_argv[io_argv.index("--host-if") + 1] == "62"
        assert io_argv[io_argv.index("--control") + 1] == \
            "/run/vpp-tpu/io-ctl.sock"
        assert f"63:afpacket:eth9" in io_argv
        sup.stop()
        # init and the IO daemon never import JAX: only the agent they
        # spawn may hold the chip
        probe = ("import sys, vpp_tpu.cmd.init_main, "
                 "vpp_tpu.cmd.io_daemon; print('jax' in sys.modules)")
        out = subprocess.run([sys.executable, "-c", probe], check=True,
                             capture_output=True, text=True).stdout
        assert out.strip() == "False"

    def test_plan_timeout_is_an_error(self, tmp_path):
        cfg = cfg_with_io(tmp_path)
        sup = InitSupervisor(cfg, None,
                             spawn=FakeSpawner(cfg, plan_on_agent=False),
                             plan_timeout_s=0.3)
        with pytest.raises(TimeoutError):
            sup.start()
        sup.stop()


class TestSupervision:
    def test_dead_agent_restart_also_restarts_io(self, tmp_path):
        """A replacement agent reclaims + recreates the shm rings, so
        the io daemon must be restarted with it — an io daemon mapped to
        the orphaned segment would pump disjoint memory."""
        cfg = cfg_with_io(tmp_path)
        spawner = FakeSpawner(cfg)
        sup = InitSupervisor(cfg, None, spawn=spawner, plan_timeout_s=2.0)
        sup.RESTART_BACKOFF_S = (0.05, 0.05, 0.05, 0.05)
        sup.start()
        first_io = sup.procs["io"]
        t = threading.Thread(target=sup.supervise, daemon=True)
        t.start()
        try:
            spawner.spawned[0].die(rc=2)  # agent crashes
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline:
                if (len(spawner.by_module("vpp_tpu.cmd.agent")) >= 2
                        and len(spawner.by_module(
                            "vpp_tpu.cmd.io_daemon")) >= 2):
                    break
                time.sleep(0.05)
            assert sup.restarts["agent"] >= 1
            assert len(spawner.by_module("vpp_tpu.cmd.agent")) >= 2
            # io restarted alongside the agent, old one torn down
            assert len(spawner.by_module("vpp_tpu.cmd.io_daemon")) >= 2
            assert first_io.terminated
        finally:
            sup.stop()
            t.join(timeout=5)
        assert not t.is_alive()

    def test_dead_io_is_restarted_alone(self, tmp_path):
        cfg = cfg_with_io(tmp_path)
        spawner = FakeSpawner(cfg)
        sup = InitSupervisor(cfg, None, spawn=spawner, plan_timeout_s=2.0)
        sup.RESTART_BACKOFF_S = (0.05,)
        sup.start()
        t = threading.Thread(target=sup.supervise, daemon=True)
        t.start()
        try:
            sup.procs["io"].die(rc=1)
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline:
                if len(spawner.by_module("vpp_tpu.cmd.io_daemon")) >= 2:
                    break
                time.sleep(0.05)
            assert len(spawner.by_module("vpp_tpu.cmd.io_daemon")) >= 2
            # the agent was never touched
            assert len(spawner.by_module("vpp_tpu.cmd.agent")) == 1
        finally:
            sup.stop()
            t.join(timeout=5)

    def test_stop_tears_down_io_before_agent(self, tmp_path):
        cfg = cfg_with_io(tmp_path)
        order = []

        class OrderedSpawner(FakeSpawner):
            def __call__(self, argv):
                p = super().__call__(argv)
                orig = p.terminate

                def term():
                    order.append(p.argv)
                    orig()

                p.terminate = term
                return p

        sup = InitSupervisor(cfg, None, spawn=OrderedSpawner(cfg),
                             plan_timeout_s=2.0)
        sup.start()
        sup.stop()
        assert len(order) == 2
        assert "vpp_tpu.cmd.io_daemon" in order[0]
        assert "vpp_tpu.cmd.agent" in order[1]


class TestUplinkPreconfig:
    def test_static_ip_and_proxy_arp(self, tmp_path):
        calls = []

        def fake_run(argv, **kw):
            calls.append(argv)

            class R:
                returncode = 0
                stdout = stderr = ""

            return R()

        cfg = cfg_with_io(tmp_path, uplink_ip="192.168.16.5/24",
                          proxy_arp=True)
        applied = configure_uplink(cfg, run=fake_run)
        assert ["ip", "link", "set", "eth9", "up"] in calls
        assert ["ip", "addr", "replace", "192.168.16.5/24",
                "dev", "eth9"] in calls
        assert ["sysctl", "-w", "net.ipv4.conf.eth9.proxy_arp=1"] in calls
        assert applied == {"interface": "eth9", "ip": "192.168.16.5/24",
                           "dhcp": False, "proxy_arp": True}

    def test_no_uplink_is_a_noop(self, tmp_path):
        cfg = AgentConfig(node_name="n1")
        applied = configure_uplink(
            cfg, run=lambda *a, **k: (_ for _ in ()).throw(
                AssertionError("must not shell out")))
        assert applied["interface"] == ""



class TestMeshBoot:
    def _cfg(self, tmp_path):
        from vpp_tpu.cmd.config import MeshConfig

        cfg = cfg_with_io(tmp_path)
        cfg.mesh = MeshConfig(nodes=2, rule_shards=1)
        return cfg

    def test_mesh_agent_and_per_node_io(self, tmp_path):
        """mesh: config -> vpp-tpu-mesh-agent is the vswitch and ONE io
        daemon boots per node plan (suffixed shm/control endpoints)."""
        cfg = self._cfg(tmp_path)
        spawner = FakeSpawner(cfg)
        sup = InitSupervisor(cfg, None, spawn=spawner, plan_timeout_s=5.0)
        # settle window is 1.5s inside read_plans
        sup.start()
        assert spawner.by_module("vpp_tpu.cmd.mesh_main")
        assert not spawner.by_module("vpp_tpu.cmd.agent")
        ios = spawner.by_module("vpp_tpu.cmd.io_daemon")
        assert len(ios) == 2
        shms = sorted(a[a.index("--shm") + 1] for a in
                      (p.argv for p in ios))
        assert shms == ["vpp-shm.0", "vpp-shm.1"]

    def test_one_io_death_respawns_only_it(self, tmp_path):
        cfg = self._cfg(tmp_path)
        spawner = FakeSpawner(cfg)
        sup = InitSupervisor(cfg, None, spawn=spawner, plan_timeout_s=5.0)
        sup.start()
        t = threading.Thread(target=sup.supervise, daemon=True)
        t.start()
        try:
            ios = spawner.by_module("vpp_tpu.cmd.io_daemon")
            ios[0].die(rc=3)
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                now = spawner.by_module("vpp_tpu.cmd.io_daemon")
                if len(now) == 3:
                    break
                time.sleep(0.05)
            assert len(spawner.by_module("vpp_tpu.cmd.io_daemon")) == 3
            # the mesh agent was NOT restarted
            assert len(spawner.by_module("vpp_tpu.cmd.mesh_main")) == 1
        finally:
            sup.stop()
            t.join(timeout=10)

    def test_mesh_agent_death_restarts_all_io(self, tmp_path):
        cfg = self._cfg(tmp_path)
        spawner = FakeSpawner(cfg)
        sup = InitSupervisor(cfg, None, spawn=spawner, plan_timeout_s=5.0)
        sup.start()
        t = threading.Thread(target=sup.supervise, daemon=True)
        t.start()
        try:
            spawner.by_module("vpp_tpu.cmd.mesh_main")[0].die(rc=2)
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline:
                if (len(spawner.by_module("vpp_tpu.cmd.mesh_main")) >= 2
                        and len(spawner.by_module(
                            "vpp_tpu.cmd.io_daemon")) >= 4):
                    break
                time.sleep(0.05)
            assert len(spawner.by_module("vpp_tpu.cmd.mesh_main")) == 2
            assert len(spawner.by_module("vpp_tpu.cmd.io_daemon")) == 4
        finally:
            sup.stop()
            t.join(timeout=10)


def test_mesh_plans_straggle_past_settle_window(tmp_path):
    """Known node count: init must wait for ALL plans even when node
    boots straggle (a settle heuristic committed to a partial set when
    writes were >1.5s apart — e.g. a host-interconnect wire wait
    between agent boots)."""
    from vpp_tpu.cmd.config import MeshConfig

    cfg = cfg_with_io(tmp_path)
    cfg.mesh = MeshConfig(nodes=2, rule_shards=1)
    spawner = FakeSpawner(cfg, plan_on_agent=False)
    sup = InitSupervisor(cfg, None, spawn=spawner, plan_timeout_s=15.0)

    def slow_agent_boots():
        # deterministic ordering, not a sleep: _clear_plan runs
        # immediately before the agent spawn, so once the spawner has
        # the mesh agent the clear is done — a plan written before it
        # would be (correctly) deleted as stale and this test would
        # time out waiting for a .0 that never returns
        deadline = time.monotonic() + 10
        while not spawner.by_module("vpp_tpu.cmd.mesh_main") \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        write_plan(cfg, _suffix=".0", shm="vpp-shm.0")
        time.sleep(3.0)   # well past the old 1.5s settle window
        write_plan(cfg, _suffix=".1", shm="vpp-shm.1")

    threading.Thread(target=slow_agent_boots, daemon=True).start()
    sup.start()
    ios = spawner.by_module("vpp_tpu.cmd.io_daemon")
    assert len(ios) == 2, "partial plan set committed"
    shms = sorted(p.argv[p.argv.index("--shm") + 1] for p in ios)
    assert shms == ["vpp-shm.0", "vpp-shm.1"]


def test_multihost_waits_for_local_plans_only(tmp_path):
    """Multi-host (mesh.coordinator set): mesh.nodes counts the WHOLE
    cluster's rows, but this host's MultiHostRuntime writes plan files
    only for the rows its local devices own. Waiting for the global
    count timed out on every host and left the deployment with no io
    daemons (ADVICE r4 #1) — the settle heuristic must apply instead."""
    from vpp_tpu.cmd.config import MeshConfig

    cfg = cfg_with_io(tmp_path)
    cfg.mesh = MeshConfig(nodes=4, rule_shards=1,
                          coordinator="10.0.0.1:1234",
                          num_processes=2, process_id=0)
    spawner = FakeSpawner(cfg, plan_on_agent=False)
    sup = InitSupervisor(cfg, None, spawn=spawner, plan_timeout_s=8.0)

    def local_rows_boot():
        # after _clear_plan, deterministically (the agent spawn
        # immediately follows the clear — same ordering discipline as
        # the straggle test above): this host owns rows 0 and 1 of
        # the 4-row cluster
        deadline = time.monotonic() + 10
        while not spawner.by_module("vpp_tpu.cmd.mesh_main") \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        write_plan(cfg, _suffix=".0", shm="vpp-shm.0")
        write_plan(cfg, _suffix=".1", shm="vpp-shm.1")

    threading.Thread(target=local_rows_boot, daemon=True).start()
    sup.start()
    try:
        ios = spawner.by_module("vpp_tpu.cmd.io_daemon")
        assert len(ios) == 2, (
            f"expected io daemons for the 2 LOCAL rows, got {len(ios)}")
        shms = sorted(p.argv[p.argv.index("--shm") + 1] for p in ios)
        assert shms == ["vpp-shm.0", "vpp-shm.1"]
    finally:
        sup.stop()
