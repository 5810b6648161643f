"""Packet-IO front-end: transports, IO daemon, and the dataplane pump.

The piece the reference gets from VPP's input/output graph nodes plus
its DPDK/AF_PACKET/TAP drivers (contiv-vswitch.conf:8-11, graph nodes in
docs/VPP_PACKET_TRACING_K8S.md:28-50): real packets in from the wire,
through the native codec into shared-memory frame rings, across the
jitted TPU pipeline, and back out rewritten.

  wire -> Transport.recv -> PacketCodec.parse -> rx IORing
       -> DataplanePump -> Dataplane.process (TPU) -> tx IORing
       -> PacketCodec.rewrite (+ VXLAN encap) -> Transport.send -> wire

The names below load on first use: the IO daemon imports this package
and must stay JAX-free, while ``DataplanePump`` drives the device.
"""

import importlib

_EXPORTS = {
    "IORing": "rings",
    "IORingPair": "rings",
    "Transport": "transport",
    "AfPacketTransport": "transport",
    "TapTransport": "transport",
    "SocketPairTransport": "transport",
    "IODaemon": "daemon",
    "DataplanePump": "pump",
    "LatencyGovernor": "governor",
    "PriorityFilter": "governor",
}

__all__ = list(_EXPORTS)


def __getattr__(name):
    if name not in _EXPORTS:
        raise AttributeError(name)
    return getattr(importlib.import_module(f"vpp_tpu.io.{_EXPORTS[name]}"),
                   name)
