"""The Pallas kernels and one fused step compile for a described v5e.

Nothing runs: the TPU compiler is handed shapes on a chip that is
described, not attached (``jax.experimental.topologies``), so the
tiling, lowering and VMEM refusals that interpret mode hides surface
here at no chip time. Kernels are compiled at the chip smoke's Phase B
widths (chip_smoke.py): 65,536 packets, 10,240 rules, the session
table at 1 << 15 slots and at the VMEM gate's largest table, and LPM
planes with several populated prefix lengths.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every xdist worker imports this
file. The persistent compilation cache stays off around the compiles.
"""

from __future__ import annotations

import math
import os
import re
from types import SimpleNamespace

import pytest

import jax
import jax.numpy as jnp

P = 65536


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any refusal means no describer
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_for(one_chip):
    """``compiled_for(fn, *shapes)`` → the compiled program, with the
    persistent cache off for the duration."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def run(fn, *shapes):
        args = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), shapes)
        return jax.jit(fn).lower(*args).compile()

    yield run
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def compile_for(compiled_for):
    """``compile_for(fn, *shapes)`` → the compiled program's HLO text."""
    return lambda fn, *shapes: compiled_for(fn, *shapes).as_text()


def S(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def test_bv_first_set_compiles(compile_for):
    from vpp_tpu.ops.acl_bv import bv_first_set

    words = 10240 // 32
    txt = compile_for(bv_first_set, *[S((P, words), jnp.uint32)] * 5)
    assert "tpu_custom_call" in txt


def test_mxu_first_match_compiles(compile_for):
    from vpp_tpu.ops.acl_mxu import PLANES, mxu_first_match

    txt = compile_for(mxu_first_match, S((P, PLANES), jnp.bfloat16),
                      S((PLANES, 10240), jnp.bfloat16),
                      S((10240,), jnp.float32))
    assert "tpu_custom_call" in txt


def test_mxu_first_match_compiles_under_shard_map(topo, compile_for):
    """The rule-sharded mesh classify runs the MXU kernel inside
    ``shard_map`` (parallel/cluster.py): the kernel's output must
    declare the mesh axes it varies over, or JAX 0.9 refuses it."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as PS

    from vpp_tpu.ops.acl_mxu import PLANES, mxu_first_match

    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("node", "rule"))
    fn = jax.shard_map(
        lambda b, c, k: mxu_first_match(b, c, k)[None],
        mesh=mesh, in_specs=(PS("node"), PS(None, "rule"), PS("rule")),
        out_specs=PS("rule", "node"))
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=NamedSharding(mesh, sp))
            for shape, dt, sp in (((4096, PLANES), jnp.bfloat16, PS("node")),
                                  ((PLANES, 1024), jnp.bfloat16,
                                   PS(None, "rule")),
                                  ((1024,), jnp.float32, PS("rule")))]
    txt = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("slots", [1 << 15, 1 << 18])
def test_sess_probe_ways_compiles(compile_for, slots):
    from vpp_tpu.ops.session import sess_probe_ways, session_pallas_fits

    ways = 4
    # 1 << 18 is the largest power-of-two table the VMEM gate admits
    assert session_pallas_fits(SimpleNamespace(sess_slots=slots,
                                               sess_ways=ways))
    assert not session_pallas_fits(SimpleNamespace(sess_slots=1 << 19,
                                                   sess_ways=ways))
    nb = slots // ways
    pkt = [S((P,), jnp.int32)] + [S((P,), jnp.uint32)] * 4
    cols = ([S((nb, ways), jnp.int32)] + [S((nb, ways), jnp.uint32)] * 4
            + [S((nb, ways), jnp.int32)])
    txt = compile_for(sess_probe_ways, *pkt, *cols, S((), jnp.int32),
                      S((), jnp.int32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("lengths,width", [(3, 5120), (33, 8192)])
def test_lpm_fused_lookup_compiles(compile_for, lengths, width):
    from vpp_tpu.ops.lpm import lpm_fused_lookup

    txt = compile_for(lpm_fused_lookup, S((P,), jnp.uint32),
                      S((lengths,), jnp.uint32), S((lengths,), jnp.int32),
                      S((lengths, width), jnp.int32),
                      S((lengths, width), jnp.int32))
    assert "tpu_custom_call" in txt


def test_step_with_every_pallas_rung_compiles(compile_for, monkeypatch):
    """A small node whose ladders select all three Pallas rungs (BV
    classifier past its rule knee, LPM past its route knee, a session
    table under the VMEM gate): the whole fused step compiles with the
    three kernels in it."""
    import vpp_tpu.ops._pallas as pallas_mod
    from vpp_tpu.ir.rule import Action, ContivRule, Protocol
    from vpp_tpu.pipeline.dataplane import Dataplane
    from vpp_tpu.pipeline.graph import make_pipeline_step
    from vpp_tpu.pipeline.tables import DataplaneConfig
    from vpp_tpu.pipeline.vector import Disposition, make_packet_vector

    monkeypatch.setattr(pallas_mod, "use_pallas", lambda: True)
    dp = Dataplane(DataplaneConfig(max_global_rules=1024, fib_slots=512,
                                   classifier_bv_min_rules=64))
    up = dp.add_uplink()
    pod = dp.add_pod_interface(("default", "pod"))
    for i in range(300):
        dp.builder.add_route(f"10.{1 + i // 256}.{i % 256}.0/24", up,
                             Disposition.REMOTE, node_id=i)
    dp.builder.add_route("10.9.0.2/32", pod, Disposition.LOCAL)
    dp.builder.set_global_table(
        [ContivRule(action=Action.PERMIT, protocol=Protocol.TCP,
                    dest_port=1000 + i) for i in range(127)]
        + [ContivRule(action=Action.DENY)])
    dp.swap()
    snap = dp.kernel_snapshot()
    assert [snap[k]["impl"] for k in ("classifier", "fib", "session")] \
        == ["pallas"] * 3
    pkts = make_packet_vector([{"src": "10.1.0.5", "dst": "10.9.0.2",
                                "proto": 6, "sport": 1000, "dport": 1001,
                                "rx_if": up}])
    # the raw step, not Dataplane's counted jit wrapper: this trace must
    # not count against the suite's compile-once contract
    step = make_pipeline_step(dp.classifier_impl, dp._skip_local,
                              fast=dp._use_fastpath,
                              fib_impl=dp.fib_impl,
                              sess_impl=dp.session_impl)
    txt = compile_for(step, dp.tables, pkts, jnp.int32(1))
    assert txt.count("tpu_custom_call") >= 3


def test_acl_local_bv_first_set_compiles(compile_for):
    """The local tables' first-set kernel at the namespaced-egress
    node's widths (10,272-rule tables: 321 words), under its own name."""
    from vpp_tpu.ops.acl_bv import acl_local_bv_first_set

    txt = compile_for(acl_local_bv_first_set,
                      *[S((2048, 321), jnp.uint32)] * 5)
    assert "%acl_local_bv_first_set" in txt


def local_table_node():
    """A node whose only policy sits in one 201-rule local table."""
    from vpp_tpu.ir.rule import Action, ContivRule, Protocol
    from vpp_tpu.pipeline.dataplane import Dataplane
    from vpp_tpu.pipeline.tables import DataplaneConfig
    from vpp_tpu.pipeline.vector import Disposition

    dp = Dataplane(DataplaneConfig(max_tables=2, max_rules=256,
                                   classifier_bv_min_rules=128))
    pod = dp.add_pod_interface(("ns", "pod"))
    dp.builder.add_route("10.9.0.2/32", pod, Disposition.LOCAL)
    slot = dp.alloc_table_slot("T")
    dp.builder.set_local_table(slot, [
        ContivRule(action=Action.PERMIT, protocol=Protocol.TCP,
                   dest_port=1000 + i) for i in range(200)]
        + [ContivRule(action=Action.DENY, protocol=Protocol.TCP)])
    dp.assign_pod_table(("ns", "pod"), "T")
    dp.swap()
    return dp, pod


def test_local_only_step_serves_the_local_kernel(compile_for, monkeypatch):
    """A node whose only policy sits in a local table: auto selects the
    pallas rung from the local table's size, and the step's local
    classify runs the local kernel (no global one: the table is empty)."""
    import vpp_tpu.ops._pallas as pallas_mod
    from vpp_tpu.pipeline.graph import make_pipeline_step
    from vpp_tpu.pipeline.vector import make_packet_vector

    monkeypatch.setattr(pallas_mod, "use_pallas", lambda: True)
    dp, pod = local_table_node()
    assert dp.builder.glb_nrules == 0
    assert dp.kernel_snapshot()["classifier"]["impl"] == "pallas"
    pkts = make_packet_vector([{"src": "10.9.0.2", "dst": "10.9.0.3",
                                "proto": 6, "sport": 1000, "dport": 1001,
                                "rx_if": pod}])
    step = make_pipeline_step(dp.classifier_impl, dp._skip_local,
                              fast=dp._use_fastpath,
                              fib_impl=dp.fib_impl,
                              sess_impl=dp.session_impl)
    txt = compile_for(step, dp.tables, pkts, jnp.int32(1))
    assert "%acl_local_bv_first_set" in txt


HLO_DTYPES = {"pred": "bool", "s8": "int8", "u8": "uint8", "s16": "int16",
              "u16": "uint16", "bf16": "bfloat16", "f16": "float16",
              "s32": "int32", "u32": "uint32", "f32": "float32"}


def entry_of(txt: str) -> dict:
    """The ENTRY computation of compiled HLO text: {name: definition}."""
    defs = {}
    for line in txt[txt.index("\nENTRY"):].splitlines()[1:]:
        m = re.match(r"\s*(ROOT )?%(\S+) = (.*)", line)
        if m:
            defs["ROOT" if m.group(1) else m.group(2)] = m.group(3)
    return defs


def copied_params(defs: dict) -> set:
    """Parameters an ENTRY ``copy`` reads, through the async copies
    that stage them in and out of VMEM."""
    out = set()
    for d in defs.values():
        m = re.search(r" copy\(%([^,)\s]+)", d)
        while m:
            src = defs.get(m.group(1), "")
            if " parameter(" in src:
                out.add(m.group(1))
                break
            m = re.search(r" (?:copy|copy-done|copy-start|bitcast)"
                          r"\(%([^,)\s]+)", src)
    return out


def root_buffers(defs: dict) -> list:
    """(dtype, dims, device bytes) of each ENTRY output, its device
    bytes padded to the tiles of the layout the compiler chose."""
    sig = defs["ROOT"].split(" tuple(")[0]
    out = []
    for dt, dims, order, tile in re.findall(
            r"([a-z]+\d+)\[([\d,]*)\]\{([\d,]*):T\(([\d,]+)\)", sig):
        dims = tuple(int(x) for x in dims.split(",") if x)
        padded = list(dims) or [1]
        minor_first = [int(x) for x in order.split(",") if x] or [0]
        for t, axis in zip([int(x) for x in tile.split(",")][::-1],
                           minor_first):
            padded[axis] = -(-padded[axis] // t) * t
        dtype = jnp.dtype(HLO_DTYPES[dt])
        out.append((dtype, dims, dtype.itemsize * math.prod(padded)))
    return out


def test_packed_step_outputs_only_written_leaves(compiled_for, monkeypatch):
    """The packed step of a local-table node on the pallas rung: the
    compiled ENTRY copies no ``acl_bv_*`` plane into an output, and its
    outputs are exactly the written table leaves, the [5, B] result
    and the aux row — in bytes, those buffers at the compiler's layouts
    plus the output tuple's index table. The all-leaf boundary, as a
    control, copies every BV plane out."""
    import vpp_tpu.ops._pallas as pallas_mod
    from vpp_tpu.pipeline.dataplane import (
        PACKED_AUX_ROWS,
        _packed_call,
        _packed_tables,
        packed_input_zeros,
    )
    from vpp_tpu.pipeline.graph import make_pipeline_step

    monkeypatch.setattr(pallas_mod, "use_pallas", lambda: True)
    dp, _pod = local_table_node()
    assert dp.kernel_snapshot()["classifier"]["impl"] == "pallas"
    step = make_pipeline_step(dp.classifier_impl, dp._skip_local,
                              fast=dp._use_fastpath,
                              fib_impl=dp.fib_impl,
                              sess_impl=dp.session_impl)
    args = (dp.tables, jnp.asarray(packed_input_zeros(256)), jnp.int32(1))
    bv = {f"tables_{f}" for f in dp.tables._fields if f.startswith("acl_bv")}

    full = compiled_for(_packed_tables(step, with_aux=True), *args)
    assert bv <= {p.rsplit(".", 1)[0]
                  for p in copied_params(entry_of(full.as_text()))}

    new = compiled_for(_packed_call(step, with_aux=True), *args)
    defs = entry_of(new.as_text())
    assert not bv & {p.rsplit(".", 1)[0] for p in copied_params(defs)}
    written = jax.eval_shape(_packed_call(step, with_aux=True), *args)[0]
    want = [(x.dtype, x.shape) for x in jax.tree.leaves(written)]
    want += [(jnp.dtype("int32"), (5, 256)),
             (jnp.dtype("int32"), (PACKED_AUX_ROWS,))]
    bufs = root_buffers(defs)
    assert [(dt, dims) for dt, dims, _b in bufs] == want
    table = -(-4 * len(bufs) // 512) * 512
    assert new.memory_analysis().output_size_in_bytes == \
        sum(b for _dt, _d, b in bufs) + table
