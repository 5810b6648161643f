"""Proxy-chained HTTP through policy at 10k rules — the nginx-istio
service-mesh scenario (VERDICT r4 Next #7).

Reference analog: tests/nginx-istio/nginx-envoy.yaml + BASELINE.md
config #5 — an HTTP client reaching nginx through an Envoy proxy, with
the mesh's policy plumbing between every hop. Here the chain is three
REAL python subprocesses that never import vpp_tpu, each interposed by
the LD_PRELOAD session shim (libvclshim.so) against one
VclAdmissionServer whose SessionRuleEngine holds a gen-policy-scale
10,240-rule set, shim configured FAIL-CLOSED:

    client --HTTP--> proxy --HTTP--> backend
      |connect:CLIENT ns    |connect:PROXY ns
      |accept: proxy port   |accept: backend port

Every arrow is two admission verdicts (connect on the client side of
the hop, accept on the server side) computed by the jitted rule
classify over the full rule set. The policy seam is load-bearing: the
client can ONLY reach the backend through the proxy, and revoking the
proxy's upstream permission breaks the chain live.
"""

from __future__ import annotations

import socket
import struct
import subprocess
import sys
import time

import pytest

from vpp_tpu.hoststack.admission import VclAdmissionServer
from vpp_tpu.hoststack.preload import vcl_env
from vpp_tpu.hoststack.scenarios import gen_policy_filler, proxy_chain_rules
from vpp_tpu.hoststack.session_rules import SessionRuleEngine

CLIENT_NS, PROXY_NS, BACKEND_NS = 11, 12, 13
N_FILLER = 10240


def ipi(a: str) -> int:
    return struct.unpack("!I", socket.inet_aton(a))[0]


LOOP = None  # set in fixture (ipi needs no jax; keep module import light)


BACKEND_CODE = r"""
import socket, sys
ls = socket.socket()
ls.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
ls.bind(("127.0.0.1", 0))
ls.listen(64)
print(ls.getsockname()[1], flush=True)
BODY = b"hello-from-backend\n"
RESP = (b"HTTP/1.1 200 OK\r\nContent-Length: %d\r\n"
        b"Connection: close\r\n\r\n" % len(BODY)) + BODY
while True:
    c, _ = ls.accept()
    try:
        buf = b""
        while b"\r\n\r\n" not in buf:
            d = c.recv(4096)
            if not d:
                break
            buf += d
        if buf:
            c.sendall(RESP)
    finally:
        c.close()
"""

PROXY_CODE = r"""
import socket, sys
upstream = ("127.0.0.1", int(sys.argv[1]))
ls = socket.socket()
ls.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
ls.bind(("127.0.0.1", 0))
ls.listen(64)
print(ls.getsockname()[1], flush=True)
while True:
    c, _ = ls.accept()
    try:
        buf = b""
        while b"\r\n\r\n" not in buf:
            d = c.recv(4096)
            if not d:
                break
            buf += d
        if not buf:
            continue
        try:
            u = socket.create_connection(upstream, timeout=10)
        except OSError:
            c.sendall(b"HTTP/1.1 502 Bad Gateway\r\n"
                      b"Content-Length: 0\r\nConnection: close\r\n\r\n")
            continue
        try:
            u.sendall(buf)
            while True:
                d = u.recv(4096)
                if not d:
                    break
                c.sendall(d)
        finally:
            u.close()
    finally:
        try:
            c.close()
        except OSError:
            pass
"""

CLIENT_CODE = r"""
import socket, sys
port = int(sys.argv[1])
s = socket.socket()
s.settimeout(15)
try:
    s.connect(("127.0.0.1", port))
except OSError:
    print("REFUSED")
    raise SystemExit(0)
s.sendall(b"GET / HTTP/1.1\r\nHost: t\r\nConnection: close\r\n\r\n")
buf = b""
try:
    while True:
        d = s.recv(4096)
        if not d:
            break
        buf += d
except OSError:
    pass
if not buf:
    print("EMPTY")
else:
    head, _, body = buf.partition(b"\r\n\r\n")
    print(head.split(b"\r\n")[0].decode(), body.decode().strip())
"""


@pytest.fixture(scope="module")
def mesh(tmp_path_factory):
    """Admission server + 10k-rule engine (shared scenario builders,
    vpp_tpu/hoststack/scenarios.py) + backend and proxy subprocesses
    under the fail-closed shim."""
    loop = ipi("127.0.0.1")
    engine = SessionRuleEngine(capacity=16384)
    engine.apply(add=gen_policy_filler(N_FILLER))
    path = str(tmp_path_factory.mktemp("vcl") / "vcl.sock")
    srv = VclAdmissionServer(engine, path).start()
    procs = []
    try:
        backend = subprocess.Popen(
            [sys.executable, "-c", BACKEND_CODE],
            env=vcl_env(path, appns_index=BACKEND_NS, fail_closed=True),
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        procs.append(backend)
        bport = int(backend.stdout.readline())
        proxy = subprocess.Popen(
            [sys.executable, "-c", PROXY_CODE, str(bport)],
            env=vcl_env(path, appns_index=PROXY_NS, fail_closed=True),
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        procs.append(proxy)
        pport = int(proxy.stdout.readline())

        chain = proxy_chain_rules(loop, CLIENT_NS, PROXY_NS, pport, bport)
        engine.apply(add=chain)
        yield engine, path, pport, bport, chain
    finally:
        # also covers PARTIAL setup failure (a subprocess that never
        # printed its port): whatever started is torn down
        for p in procs:
            p.kill()
        for p in procs:
            p.wait(timeout=10)
        srv.stop()


def run_client(path, port, timeout=60):
    out = subprocess.run(
        [sys.executable, "-c", CLIENT_CODE, str(port)],
        env=vcl_env(path, appns_index=CLIENT_NS, fail_closed=True),
        capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-500:]
    return out.stdout.strip()


def test_http_through_proxy_chain(mesh):
    """The full chain serves: client -> proxy -> backend, four
    admission verdicts against the 10k-rule set per request chain."""
    engine, path, pport, bport, _ = mesh
    assert run_client(path, pport) == "HTTP/1.1 200 OK hello-from-backend"


def test_direct_backend_access_denied(mesh):
    """The mesh seam: the client's namespace has no permit for the
    backend port — bypassing the proxy must fail at connect()."""
    engine, path, pport, bport, _ = mesh
    assert run_client(path, bport) == "REFUSED"


def test_revoking_proxy_upstream_breaks_chain_live(mesh):
    """Policy update mid-flight: deleting the proxy->backend permit
    turns the chain into 502 (the proxy's own connect is refused);
    re-adding restores 200 — no process restarts anywhere."""
    engine, path, pport, bport, chain = mesh
    upstream_allow = chain[2]
    engine.apply(delete=[upstream_allow])
    try:
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            got = run_client(path, pport)
            if got == "HTTP/1.1 502 Bad Gateway":
                break
            time.sleep(0.2)
        assert got == "HTTP/1.1 502 Bad Gateway", got
    finally:
        engine.apply(add=[upstream_allow])
    assert run_client(path, pport) == "HTTP/1.1 200 OK hello-from-backend"
