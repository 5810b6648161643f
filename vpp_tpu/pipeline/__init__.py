"""The TPU data-plane pipeline: packet vectors, device tables, fused step.

Reference analog: VPP's graph-node packet pipeline (256-packet frames
flowing dpdk-input → ethernet-input → ip4-input → acl → nat44 →
ip4-lookup → interface-tx; see SURVEY.md §3.5). Here each graph node is a
vectorized JAX/Pallas stage over a struct-of-arrays packet vector, the
whole chain is one jitted function, and tables live in HBM as a pytree
swapped transactionally by renderer commits.

The names below load on first use, so importing a JAX-free submodule
(``vpp_tpu.pipeline.config``) does not import JAX.
"""

import importlib

_EXPORTS = {
    "VEC": "vector",
    "Disposition": "vector",
    "PacketVector": "vector",
    "make_packet_vector": "vector",
    "DataplaneConfig": "config",
    "DataplaneTables": "tables",
    "InterfaceType": "tables",
}

__all__ = list(_EXPORTS)


def __getattr__(name):
    if name not in _EXPORTS:
        raise AttributeError(name)
    return getattr(importlib.import_module(
        f"vpp_tpu.pipeline.{_EXPORTS[name]}"), name)
