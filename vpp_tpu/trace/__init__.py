"""Tracing & profiling.

Reference analogs: the VPP packet tracer (`trace add <node> N` + `show
trace`, docs/VPP_PACKET_TRACING_K8S.md:20-50), span tracing over the
config path (``vpp_tpu.trace.spans``), and the served path's host
stage timer (``vpp_tpu.trace.timed``), whose profiler spans sit on
the device trace's clock next to the fused step's per-stage
``jax.named_scope``s — the `show run` analog (:28-50) under XLA.

Re-exports resolve lazily (PEP 562): the packet tracer pulls in the
jax-backed pipeline, and light processes (kvserver, KSR) that only need
``trace.spans`` must not pay that import.
"""

_LAZY = {
    "PacketTracer": ("vpp_tpu.trace.tracer", "PacketTracer"),
    "TraceEntry": ("vpp_tpu.trace.tracer", "TraceEntry"),
    "Span": ("vpp_tpu.trace.spans", "Span"),
    "SpanTracer": ("vpp_tpu.trace.spans", "SpanTracer"),
}

__all__ = sorted(_LAZY)


def __getattr__(name):
    try:
        mod_name, attr = _LAZY[name]
    except KeyError:
        raise AttributeError(name) from None
    import importlib

    value = getattr(importlib.import_module(mod_name), attr)
    globals()[name] = value  # cache for subsequent lookups
    return value
