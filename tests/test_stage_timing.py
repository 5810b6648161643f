"""Stage timing of the served path: the `show run` analog under XLA.

Two instruments, one per side of the device boundary:

- the fused step traces every graph stage under a fixed
  ``jax.named_scope`` (pipeline/graph.py STAGE_SCOPES), so the compiled
  program's ``op_name`` metadata — and a device trace — splits the one
  fused program by stage;
- the pump's host stages are timed by ``vpp_tpu.trace.timed.Timed``:
  one interval feeds both a ``DataplanePump.stats`` counter and a
  profiler span on the device trace's clock.
"""

from __future__ import annotations

import glob
import os
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from wire import make_frame

from vpp_tpu.io import DataplanePump, IORingPair
from vpp_tpu.native.pktio import PacketCodec
from vpp_tpu.pipeline.dataplane import (
    Dataplane,
    _packed_call,
    packed_input_zeros,
)
from vpp_tpu.pipeline.graph import STAGE_SCOPES, make_pipeline_step
from vpp_tpu.pipeline.tables import DataplaneConfig
from vpp_tpu.pipeline.vector import VEC, Disposition, make_packet_vector
from vpp_tpu.trace.timed import Timed

CLIENT_IP = "10.1.1.2"
SERVER_IP = "10.1.1.3"

# the counters the stage timer adds to DataplanePump.stats
NEW_COUNTERS = ("t_dp_upload", "t_dp_call", "t_dispatch_cpu",
                "t_fetch_queue", "t_reorder_wait", "t_resident",
                "rx_backlog_sum")


# --- named scopes per graph stage --------------------------------------

def _scoped_dp():
    """Every optional stage compiled in: tenancy, ML scoring, overlay."""
    dp = Dataplane(DataplaneConfig(
        max_tables=2, max_rules=8, max_global_rules=8, max_ifaces=8,
        fib_slots=16, sess_slots=256, nat_mappings=2, nat_backends=4,
        tenancy="on", ml_stage="score", overlay="vxlan"))
    dp.swap()
    return dp


def _op_names(lowered) -> set:
    """Every ``op_name`` of the lowered program's HLO metadata."""
    text = lowered.as_text(dialect="hlo", debug_info=True)
    return set(re.findall(r'op_name="([^"]*)"', text))


def _scopes_seen(op_names: set) -> set:
    return {s for s in STAGE_SCOPES
            if any(f"/{s}/" in name for name in op_names)}


@pytest.mark.parametrize("fast", [False, True], ids=["full", "two_tier"])
def test_packed_step_carries_a_scope_per_stage(fast):
    """The packed step of both tiers (the full chain alone, and the
    two-tier dispatcher with its fast kernel) traces every stage it
    compiles under its scope; the overlay rides the plain form only."""
    dp = _scoped_dp()
    fn = make_pipeline_step("dense", False, fast, 256, ml_mode="score",
                            tnt_mode="on")
    run = jax.jit(_packed_call(fn, with_aux=True))
    lowered = run.lower(dp.tables, jnp.asarray(packed_input_zeros(VEC)),
                        jnp.int32(1))
    seen = _scopes_seen(_op_names(lowered))
    assert seen == set(STAGE_SCOPES) - {"overlay"}


def test_overlay_step_carries_every_scope():
    dp = _scoped_dp()
    fn = make_pipeline_step("dense", False, True, 256, ml_mode="score",
                            tnt_mode="on", overlay="vxlan")
    pkts = make_packet_vector([dict(src=CLIENT_IP, dst=SERVER_IP, proto=6,
                                    sport=1, dport=80, rx_if=1)])
    lowered = jax.jit(fn).lower(dp.tables, pkts, jnp.int32(1), pkts,
                                jnp.full(pkts.valid.shape, -1, jnp.int32))
    names = _op_names(lowered)
    assert _scopes_seen(names) == set(STAGE_SCOPES)
    # the tx-side encap nests inside the shared tail
    assert any("/tail/overlay/" in n for n in names)


# --- the host stage timer ----------------------------------------------

def test_timed_adds_to_stats_and_emits_nothing_without_profiler():
    from jax.profiler import TraceAnnotation

    assert not TraceAnnotation.is_enabled()  # no profiler session
    stats = {"t": 0.0, "cpu": 0.0}
    lock = threading.Lock()
    with Timed("test.stage", stats, "t", lock=lock, cpu_key="cpu") as t:
        time.sleep(0.01)
        sum(range(20000))
    assert stats["t"] == pytest.approx(t.t1 - t.t0)
    assert stats["t"] >= 0.01
    # the sleep ran no CPU: thread time stays below wall time
    assert 0.0 < stats["cpu"] < stats["t"]
    # a stage that raised or gave up adds nothing; a span alone keeps
    # no counter
    before = dict(stats)
    with pytest.raises(RuntimeError):
        with Timed("test.stage", stats, "t"):
            raise RuntimeError("boom")
    with Timed("test.stage", stats, "t") as t:
        t.cancel()
    with Timed("test.stage"):
        pass
    assert stats == before


# --- the pump's counters on a CPU run ----------------------------------

def _forwarding_dp():
    dp = Dataplane(DataplaneConfig())
    a = dp.add_pod_interface(("default", "a"))
    b = dp.add_pod_interface(("default", "b"))
    dp.builder.add_route(f"{CLIENT_IP}/32", a, Disposition.LOCAL)
    dp.builder.add_route(f"{SERVER_IP}/32", b, Disposition.LOCAL)
    dp.swap()
    return dp, a


def _serve(mode: str, n_frames: int, per: int, trace_dir=None) -> dict:
    """Push ``n_frames`` frames of ``per`` packets through a pump and
    drain them; returns the pump's stats after stop(). With
    ``max_batch`` VEC and ``per`` VEC a batch holds exactly one frame."""
    dp, a = _forwarding_dp()
    rings = IORingPair(n_slots=32)
    codec = PacketCodec()
    scratch = np.zeros((VEC, rings.rx.snap), np.uint8)
    pump = DataplanePump(dp, rings, mode=mode, max_batch=VEC)
    pump.warm()
    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir)
    pump.start()
    got = 0
    try:
        for k in range(n_frames):
            frames = [make_frame(CLIENT_IP, SERVER_IP, proto=17,
                                 sport=20000 + k, dport=1000 + j)
                      for j in range(per)]
            cols, n = codec.parse(frames, a, scratch)
            assert rings.rx.push(cols, n, payload=scratch)
        deadline = time.monotonic() + 120
        while got < n_frames and time.monotonic() < deadline:
            f = rings.tx.peek()
            if f is None:
                time.sleep(0.002)
                continue
            rings.tx.release()
            got += 1
    finally:
        assert pump.stop(join_timeout=60)
        if trace_dir is not None:
            jax.profiler.stop_trace()
        rings.close()
    assert got == n_frames
    return dict(pump.stats)


@pytest.fixture(scope="module")
def dispatch_stats():
    return _serve("dispatch", n_frames=6, per=VEC)


@pytest.mark.parametrize("mode", ["dispatch", "persistent"])
def test_pump_counters_present_and_non_negative(mode, dispatch_stats):
    stats = (dispatch_stats if mode == "dispatch"
             else _serve("persistent", n_frames=4, per=8))
    for k in NEW_COUNTERS:
        assert k in stats, k
        assert stats[k] >= 0, (k, stats[k])
    assert stats["t_fetch_queue"] > 0.0
    assert stats["t_resident"] > 0.0
    assert stats["rx_backlog_sum"] >= stats["batches"] > 0
    if mode == "dispatch":
        assert stats["t_dp_upload"] > 0.0 and stats["t_dp_call"] > 0.0
    else:
        # the ring pump never calls process_packed
        assert stats["t_dp_upload"] == stats["t_dp_call"] == 0.0


def test_pump_counters_add_up(dispatch_stats):
    s = dispatch_stats
    # the dataplane's upload and step call nest inside the dispatch
    assert s["t_dp_upload"] + s["t_dp_call"] <= s["t_dispatch"]
    # CPU time over the same intervals cannot exceed their wall time
    # (slack: the two clocks tick at different granularities)
    assert s["t_dispatch_cpu"] <= s["t_dispatch"] + 1e-3
    # one frame per batch: a frame's residence spans its batch's pack,
    # dispatch, queueing, fetch and write
    assert s["frames"] == s["batches"] == 6
    serial = s["t_pack"] + s["t_fetch_wait"] + s["t_write"]
    assert s["t_resident"] / s["frames"] >= serial / s["batches"]


def test_profiler_trace_carries_pump_spans(tmp_path):
    """Under a CPU profiler session the host plane carries the pump's
    and the dataplane's spans, on the profiler's own clock."""
    from jax.profiler import ProfileData

    _serve("dispatch", n_frames=3, per=8, trace_dir=str(tmp_path))
    files = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert files
    names = set()
    for plane in ProfileData.from_file(files[-1]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(e.name for e in line.events)
    for span in ("pump.take", "pump.pack", "pump.dispatch", "dp.upload",
                 "dp.step_call", "pump.fetch_wait", "pump.fetch",
                 "pump.write"):
        assert span in names, span
