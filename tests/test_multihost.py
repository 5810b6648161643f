"""Multi-host (DCN) fabric: the cluster step across REAL separate JAX
processes.

Two worker processes (2 virtual CPU devices each) form one 4-node
cluster mesh via jax.distributed; each stages only its local nodes,
publication and stepping are collective. Traffic crosses the
process boundary through the same all_to_all fabric the single-process
mesh uses — on TPU pods the identical program rides ICI within a host
and DCN between hosts (reference analog: the VXLAN full-mesh between
DaemonSet replicas, plugins/contiv/node_events.go:184-250).
"""

import contextlib
import json
import os
import socket
import subprocess
import sys
import time

import pytest

# slow: each case boots 2 real jax.distributed worker processes and
# compiles the cluster program per process — minutes of wall clock
# that the tier-1 `-m 'not slow'` budget cannot absorb now that the
# mesh suite actually RUNS on this toolchain (ISSUE 12 un-skipped it).
# The multi-process fabric additionally needs a CPU backend with
# cross-process collectives (newer jaxlib); `make chaos`-style full
# runs and TPU-pod deployments exercise these.
pytestmark = pytest.mark.slow

HERE = os.path.dirname(os.path.abspath(__file__))


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _worker_env():
    """Workers set their own JAX env; scrub the conftest's 8-device
    forcing so distributed init is clean."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    return env


def _collect_verdicts(procs, timeout: float):
    """communicate() every worker, assert clean exits, parse the
    VERDICT lines; reaps everyone on the way out — a failed worker
    must not orphan its peer inside a jax.distributed collective."""
    outs = {}
    try:
        for pid, p in enumerate(procs):
            out, err = p.communicate(timeout=timeout)
            assert p.returncode == 0, f"worker {pid}: {err[-800:]}"
            lines = [ln for ln in out.splitlines()
                     if ln.startswith("VERDICT ")]
            assert lines, (f"worker {pid} printed no VERDICT line; "
                           f"stderr: {err[-800:]}")
            outs[pid] = json.loads(lines[-1][len("VERDICT "):])
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait(timeout=30)
    return outs


def _run_workers(script: str, extra_args=(), n_procs: int = 2,
                 timeout: float = 240):
    """Spawn the worker processes and collect their VERDICT lines."""
    env = _worker_env()
    coord_port = _free_port()
    procs = [
        subprocess.Popen(
            [sys.executable, os.path.join(HERE, script), str(pid),
             str(n_procs), str(coord_port), *map(str, extra_args)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)
        for pid in range(n_procs)
    ]
    return _collect_verdicts(procs, timeout)


def test_two_process_fabric():
    outs = _run_workers("mh_worker.py")

    # P0 fabric-routed all three packets
    assert outs[0]["local_nodes"] == [0, 1]
    assert outs[0]["sent_remote"] == 3
    # P1: pod2 got its packet on the right interface; node 3's global
    # table let port 80 through and dropped port 22
    assert outs[1]["local_nodes"] == [2, 3]
    assert outs[1]["pod2_delivered"] == 1
    assert outs[1]["pod2_txif_ok"] and outs[1]["pod2_dst_ok"]
    assert outs[1]["pod3_delivered"] == 1
    assert outs[1]["node3_acl_drops"] == 1
    # step 2: the reply crossed back P1 -> P0
    assert outs[0]["reply_delivered"] == 1


@contextlib.contextmanager
def _kvserver(tmp_path):
    """Spawn a real TCP kvserver; yields its port, reaps on exit."""
    port_file = str(tmp_path / "kv.port")
    kv = subprocess.Popen(
        [sys.executable, "-m", "vpp_tpu.cmd.kvserver", "--host",
         "127.0.0.1", "--port", "0", "--port-file", port_file],
        env=_worker_env())
    try:
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and not os.path.exists(port_file):
            assert kv.poll() is None, \
                f"kvserver died at startup (rc={kv.returncode})"
            time.sleep(0.2)
        assert os.path.exists(port_file), "kvserver never wrote its port"
        yield open(port_file).read().strip()
    finally:
        kv.kill()
        kv.wait(timeout=30)


def test_lockstep_commit_across_processes(tmp_path):
    """Control-plane half of multi-host: process 1 stages a policy
    change on its node and requests a commit through the shared
    kvstore; the LockstepDriver's collective min-agreement makes BOTH
    processes publish on the same tick — cross-process traffic that
    flowed on tick 1 is cut off cluster-wide from tick 2."""
    with _kvserver(tmp_path) as kv_port:
        outs = _run_workers("mh_lockstep_worker.py", [kv_port])

    v = outs[1]
    assert v["t1_delivered"] == 1          # flowing before the commit
    assert v["t2_epoch"] == 2              # both published on tick 2
    assert v["t2_delivered"] == 0          # cut off the same tick
    assert v["t2_acl_drops"] == 1
    assert v["t3_delivered"] == 0
    assert outs[0]["applied"] == 1 and outs[1]["applied"] == 1


def test_deployed_runtime_across_processes(tmp_path):
    """The DEPLOYED multi-host form (vpp-tpu-mesh-agent --coordinator
    shape): real ContivAgents on each process over a shared kvstore —
    CNI pod adds, node events resolving peers to mesh positions across
    the process boundary, fabric delivery, then a renderer-driven
    policy cutoff — every commit riding LockstepDriver epochs."""
    with _kvserver(tmp_path) as kv_port:
        outs = _run_workers("mh_runtime_worker.py", [kv_port])

    assert outs[0]["stage1_ok"] is True
    assert outs[1]["stage1_delivered"] >= 1       # fabric worked
    assert outs[1]["stage2_new_deliveries"] == 0  # policy cut it off
    assert outs[1]["stage2_acl_drops"] >= 1


def test_wire_path_across_processes(tmp_path):
    """io.enabled multi-host: real wire frames (Ethernet/IP/UDP bytes)
    pushed into one host's per-node rx ring ride the fabric — headers
    AND payload — across the process boundary and surface on the
    destination host's tx ring with the UDP body intact; a
    renderer-driven deny then cuts the wire path. The ClusterPump runs
    tick-driven (writer thread only), so its collective wire step
    interleaves deterministically with the lockstep driver."""
    with _kvserver(tmp_path) as kv_port:
        outs = _run_workers("mh_wire_worker.py", [kv_port])

    assert outs[0]["stage1_ok"] is True
    assert outs[0]["idle_steps_flat"] is True   # fleet-idle skips steps
    assert outs[1]["wire_delivered"] >= 1
    assert outs[1]["commit_stepped"] is True    # commit tick always steps
    assert outs[1]["stage2_cut"] is True


def test_mxu_selection_and_equivalence():
    """publish() agrees on the MXU classifier fleet-wide at
    bit-plane-compatible scale and its verdicts match the dense path
    packet-for-packet (the multi-host analog of the cluster MXU
    equivalence tests)."""
    outs = _run_workers("mh_mxu_worker.py", n_procs=1,
                        timeout=480)  # two clusters +
    # dense AND MXU step compiles share one core
    v = outs[0]
    assert v["mxu_selected"] is True
    assert v["verdicts_equal"] is True
    assert v["drop_acl"] >= 1        # some flows hit DENY rules
    assert v["delivered"] >= 1       # and some flows got through


def test_lockstep_survives_store_failover(tmp_path):
    """The multi-host fleet's coordination store dies mid-lockstep:
    witness-arbitrated failover promotes the standby, the workers'
    clients fail over (reads never stopped; writes resume at the
    bumped fencing epoch), and a policy commit REQUESTED THROUGH THE
    NEW PRIMARY still publishes on the same collective tick on both
    processes — the fenced store is transparent to the SPMD control
    loop (kvstore/witness.py + docs/MULTIHOST.md note)."""
    import signal

    from vpp_tpu.kvstore.client import RemoteKVStore
    from vpp_tpu.kvstore.witness import WitnessClient

    env = _worker_env()

    reap = []  # every spawned process, in spawn order — the finally
    #            tears down whatever managed to start, so a failed
    #            LATER spawn can't orphan the earlier servers

    def _spawn_store(name, argv):
        pf = str(tmp_path / f"{name}.port")
        p = subprocess.Popen(
            [sys.executable, "-m", argv[0], *argv[1:],
             "--port-file", pf], env=env)
        reap.append(p)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and not os.path.exists(pf):
            assert p.poll() is None, f"{name} died at startup"
            time.sleep(0.2)
        assert os.path.exists(pf), f"{name} never wrote its port"
        return p, int(open(pf).read())

    cli = None
    procs = []
    try:
        witness, w_port = _spawn_store("w", [
            "vpp_tpu.cmd.kvwitness", "--host", "127.0.0.1",
            "--port", "0"])
        primary, kv_port = _spawn_store("kv", [
            "vpp_tpu.cmd.kvserver", "--host", "127.0.0.1", "--port", "0",
            "--witness", f"127.0.0.1:{w_port}", "--fence-ttl", "6"])
        standby, sb_port = _spawn_store("sb", [
            "vpp_tpu.cmd.kvserver", "--host", "127.0.0.1", "--port", "0",
            "--follow", f"127.0.0.1:{kv_port}",
            "--witness", f"127.0.0.1:{w_port}",
            "--fence-ttl", "6", "--promote-after", "3"])
        store_url = f"tcp://127.0.0.1:{kv_port},127.0.0.1:{sb_port}"

        coord_port = _free_port()
        procs = [
            subprocess.Popen(
                [sys.executable,
                 os.path.join(HERE, "mh_lockstep_failover_worker.py"),
                 str(pid), "2", str(coord_port), store_url],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True)
            for pid in range(2)
        ]
        cli = RemoteKVStore(
            "127.0.0.1", kv_port, request_timeout=60.0,
            reconnect_timeout=60.0,
            fallbacks=[("127.0.0.1", sb_port)])
        # both workers mid-run (tick 1 done) before the kill
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            if cli.get("mhf/ready/0") == 1 and cli.get("mhf/ready/1") == 1:
                break
            assert all(p.poll() is None for p in procs), \
                "a worker died before the failover"
            time.sleep(0.5)
        else:
            raise AssertionError("workers never reached the ready point")

        primary.send_signal(signal.SIGKILL)
        primary.wait(timeout=15)
        wc = WitnessClient(f"127.0.0.1:{w_port}")
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            st = wc.status()
            if st["primary"] == f"127.0.0.1:{sb_port}" and st["epoch"] >= 1:
                break
            time.sleep(0.5)
        else:
            raise AssertionError(f"standby never promoted: {wc.status()}")
        cli.put("mhf/go", 1)   # lands on the NEW primary, fenced

        outs = _collect_verdicts(procs, timeout=420)
    finally:
        if cli is not None:
            cli.close()
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait(timeout=30)
        for p in reversed(reap):
            if p.poll() is None:
                p.terminate()
        for p in reap:
            try:
                p.wait(timeout=15)
            except subprocess.TimeoutExpired:
                p.kill()

    for pid in (0, 1):
        # exactly ONE promotion happened: the primary adopted at epoch
        # 0 (renew, no bump), the standby's granted claim bumped to 1,
        # and both workers' post-failover writes carry it
        assert outs[pid]["fence_epoch"] == 1
        assert outs[pid]["applied"] == 1          # commit applied once
        assert outs[pid]["t3_epoch"] == 2         # same tick, both procs
    v = outs[1]
    assert v["t1_delivered"] == 1     # flowing before the failover
    assert v["t2_delivered"] == 1     # still flowing right after it
    assert v["t3_delivered"] == 0     # cut by the post-failover commit
