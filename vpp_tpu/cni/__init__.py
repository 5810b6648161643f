"""CNI subsystem: the pod-wiring path of the framework.

Reference analogs: the contiv plugin's remoteCNIserver
(plugins/contiv/remote_cni_server.go), the containeridx persisted index
(plugins/contiv/containeridx), and the contiv-cni shim executable
(cmd/contiv-cni/contiv_cni.go). kubelet invokes the shim per pod
sandbox; the shim forwards Add/Delete to the node agent's CNI server,
which allocates an IP (IPAM), wires a dataplane interface + route, and
persists the container config for restart resync.

The names below load on first use: the IO daemon's control server
imports ``vpp_tpu.cni.transport`` and must stay JAX-free.
"""

import importlib

_EXPORTS = {
    "CNIReply": "model",
    "CNIRequest": "model",
    "ContainerConfig": "containeridx",
    "ContainerIndex": "containeridx",
    "RemoteCNIServer": "server",
    "ResultCode": "model",
}

__all__ = list(_EXPORTS)


def __getattr__(name):
    if name not in _EXPORTS:
        raise AttributeError(name)
    return getattr(importlib.import_module(f"vpp_tpu.cni.{_EXPORTS[name]}"),
                   name)
