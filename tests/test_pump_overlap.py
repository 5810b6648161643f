"""Overlapped fetch ladder + adaptive chaining (ISSUE 1 tentpole).

The pump's staged pipeline must hide fetch latency behind the in-flight
window WITHOUT changing observable semantics: delivery stays in-order
and loss-free under a slow result transport, dispatch backpressures at
``max_inflight`` instead of growing unboundedly, a chained fold
produces bit-identical per-frame results to unchained dispatches, and
persistent-mode stop() joins cleanly with traffic still in flight
(the ADVICE r5 shutdown race).
"""

from __future__ import annotations

import time

import numpy as np
import pytest

from wire import make_frame

from vpp_tpu.io import DataplanePump, IORingPair
from vpp_tpu.native.pktio import PacketCodec
from vpp_tpu.pipeline.dataplane import Dataplane
from vpp_tpu.pipeline.tables import DataplaneConfig
from vpp_tpu.pipeline.vector import VEC, Disposition

CLIENT_IP = "10.1.1.2"
SERVER_IP = "10.1.1.3"


def make_forwarding_dp():
    dp = Dataplane(DataplaneConfig())
    a = dp.add_pod_interface(("default", "a"))
    b = dp.add_pod_interface(("default", "b"))
    dp.builder.add_route(f"{CLIENT_IP}/32", a, Disposition.LOCAL)
    dp.builder.add_route(f"{SERVER_IP}/32", b, Disposition.LOCAL)
    dp.swap()
    return dp, a, b


def push_frames(rings, rx_if, n_frames, per=8, codec=None, scratch=None):
    """n_frames rx frames, frame k tagged sport=20000+k so order and
    identity survive the trip."""
    codec = codec or PacketCodec()
    if scratch is None:
        scratch = np.zeros((VEC, rings.rx.snap), np.uint8)
    for k in range(n_frames):
        frames = [
            make_frame(CLIENT_IP, SERVER_IP, proto=17, sport=20000 + k,
                       dport=1000 + k * per + j)
            for j in range(per)
        ]
        cols, n = codec.parse(frames, rx_if, scratch)
        assert rings.rx.push(cols, n, payload=scratch)


def drain(rings, want, timeout=180):
    got = []
    deadline = time.monotonic() + timeout
    while len(got) < want and time.monotonic() < deadline:
        f = rings.tx.peek()
        if f is None:
            time.sleep(0.002)
            continue
        got.append((f.cols["sport"][:f.n].copy(),
                    f.cols["dport"][:f.n].copy(),
                    f.cols["rx_if"][:f.n].copy(), f.n))
        rings.tx.release()
    return got


class TestSlowFetchOverlap:
    def test_in_order_loss_free_under_slow_fetch(self):
        """Fault injection: every result fetch pays an extra delay
        (the remote-transport RTT analog), varied per batch so fetch
        COMPLETIONS happen out of dispatch order across the worker
        pool — the tx writer's reorder buffer must still deliver every
        frame exactly once, in dispatch order."""
        dp, a, b = make_forwarding_dp()
        rings = IORingPair(n_slots=32)
        n_frames, per = 12, 8
        push_frames(rings, a, n_frames, per)
        pump = DataplanePump(
            dp, rings, max_batch=VEC, fetch_workers=4, max_inflight=4,
            # batches 0,1,2,... sleep 60/20/40/... ms: batch 1 is ready
            # before batch 0, exercising the reorder path
            fetch_delay=lambda seq: (0.06, 0.02, 0.04)[seq % 3],
        )
        pump.warm()
        pump.start()
        try:
            got = drain(rings, n_frames)
            assert len(got) == n_frames
            for k, (sports, dports, tx_ifs, n) in enumerate(got):
                assert n == per
                assert (sports == 20000 + k).all()  # dispatch order
                assert list(dports) == [1000 + k * per + j
                                        for j in range(per)]
                assert (tx_ifs == b).all()
            assert pump.stats["frames"] == n_frames
            assert pump.stats["pkts"] == n_frames * per
            assert pump.stats["batch_errors"] == 0
            # the delay was experienced as overlapped wait, not copy
            assert pump.stats["t_fetch_wait"] > 0.0
        finally:
            assert pump.stop()
            rings.close()

    def test_backpressure_engages_at_max_inflight(self):
        """With fetches wedged, the dispatch stage must stop at the
        in-flight cap (queue capacity + one batch per fetch worker
        already holding an item) and leave the rest of the backlog in
        the rx ring, not dispatch it all blind."""
        dp, a, _b = make_forwarding_dp()
        rings = IORingPair(n_slots=64)
        # 40 × 64 pkts at a VEC-pkt batch cap = ten device batches of
        # backlog: far more than the window holds, so the cap is
        # actually contended (4-pkt frames would coalesce into ONE
        # batch and never touch it)
        n_frames = 40
        push_frames(rings, a, n_frames, per=64)
        max_inflight, workers = 3, 2
        pump = DataplanePump(
            dp, rings, max_batch=VEC, fetch_workers=workers,
            max_inflight=max_inflight, fetch_delay=0.4,
        )
        pump.warm()
        pump.start()
        try:
            # let the window fill: dispatch is far faster than the
            # wedged fetches, so it reaches the cap quickly — poll for
            # it (a loaded host can stretch that past any fixed sleep)
            deadline = time.monotonic() + 20.0
            while (pump.stats["inflight_peak"] < max_inflight
                   and time.monotonic() < deadline):
                time.sleep(0.02)
            # hard ceiling: the queue holds max_inflight, each fetch
            # worker can hold one dequeued item, and the writer can
            # hold one completed-but-unwritten item
            cap = max_inflight + workers + 1
            assert pump.stats["inflight_peak"] <= cap
            assert pump.stats["inflight"] >= 1  # window actually in use
            with pump._held_lock:
                held = len(pump._taken) + len(pump._done_rids)
            assert held < n_frames  # backlog stayed in the ring
            # and the backlog still drains loss-free afterwards
            got = drain(rings, n_frames)
            assert len(got) == n_frames
            for k, (sports, _d, _i, n) in enumerate(got):
                assert n == 64
                assert (sports == 20000 + k).all()
            assert pump.stats["inflight_peak"] <= cap
        finally:
            assert pump.stop()
            rings.close()


    def test_reorder_buffer_counts_against_the_window(self):
        """One fetch stalls while the other worker completes every
        later batch: those park in the writer's reorder buffer, and
        each frees a queue slot. The dispatch stage must still stop
        at the window (queue + one per fetch worker + one at the
        writer) instead of refilling the queue behind the stall."""
        dp, a, _b = make_forwarding_dp()
        rings = IORingPair(n_slots=64)
        n_frames = 40  # ten VEC batches of 4 x 64 pkts
        push_frames(rings, a, n_frames, per=64)
        max_inflight, workers = 2, 2
        pump = DataplanePump(
            dp, rings, max_batch=VEC, fetch_workers=workers,
            max_inflight=max_inflight,
            fetch_delay=lambda seq: 1.0 if seq == 0 else 0.01,
        )
        pump.warm()
        pump.start()
        try:
            got = drain(rings, n_frames)
            assert [int(s[0]) for s, _d, _i, _n in got] == \
                [20000 + k for k in range(n_frames)]
            assert pump.stats["inflight_peak"] <= max_inflight + workers + 1
            assert pump.stats["batch_errors"] == 0
        finally:
            assert pump.stop()
            rings.close()


class TestFullBatchesUnderBacklog:
    @pytest.mark.parametrize("per, holds_back", [
        (VEC // 8, True),    # a full batch is 8 frames of the hold cap's 28
        (VEC // 16, False),  # 16 frames: two in flight leave no room
    ])
    def test_backlog_batches_by_frame_size(self, per, holds_back):
        """Frames trickle in while every fetch is slow, so two or more
        batches stay in flight. Where a full batch needs at most a
        third of the ring's hold cap, the pump leaves short groups to
        grow in the ring and dispatches full max_batch groups, not one
        batch per arriving frame. Smaller frames could never fill one
        while two are in flight: they are dispatched as they come and
        the in-flight window deepens past two. Every frame is
        delivered in order either way."""
        import threading

        dp, a, b = make_forwarding_dp()
        rings = IORingPair(n_slots=32)  # hold cap 28
        n_frames, full = 48, VEC // per
        pump = DataplanePump(dp, rings, max_batch=VEC,
                             fetch_workers=1, max_inflight=8,
                             fetch_delay=0.1)
        pump.warm()

        def trickle():
            codec = PacketCodec()
            scratch = np.zeros((VEC, rings.rx.snap), np.uint8)
            for k in range(n_frames):
                frames = [make_frame(CLIENT_IP, SERVER_IP, proto=17,
                                     sport=20000 + k, dport=1000 + j)
                          for j in range(per)]
                cols, n = codec.parse(frames, a, scratch)
                while not rings.rx.push(cols, n, payload=scratch):
                    time.sleep(0.001)
                time.sleep(0.005)

        pusher = threading.Thread(target=trickle, daemon=True)
        pump.start()
        pusher.start()
        try:
            got = drain(rings, n_frames)
            pusher.join(timeout=30)
            assert not pusher.is_alive()
            assert [int(s[0]) for s, _d, _i, _n in got] == \
                [20000 + k for k in range(n_frames)]
            assert all((i == b).all() for _s, _d, i, _n in got)
            if holds_back:
                # at most two short batches open the run (nothing in
                # flight yet) and one may close it; the rest are full
                assert pump.stats["batches"] <= 3 + n_frames // full
                assert pump.stats["max_coalesce"] == full
            else:
                assert pump.stats["inflight_peak"] > 2
            assert pump.stats["batch_errors"] == 0
        finally:
            assert pump.stop()
            rings.close()


    def test_refilled_ring_is_taken_beside_the_window(self):
        """Under saturation a freed rx slot is refilled at once, so the
        ring's pending count does not change as batches complete,
        though its frames are new. Three batches of small frames hold
        the pump's whole share of a full ring; when the first
        completes, each slot it frees is refilled inside the release.
        The new frames must be dispatched beside the two batches still
        in flight, not only once fewer than two are."""
        import threading

        dp, a, _b = make_forwarding_dp()
        rings = IORingPair(n_slots=64)  # hold cap 60
        pump = DataplanePump(
            dp, rings, max_batch=2048, fetch_workers=8, max_inflight=64,
            fetch_delay=lambda seq: 1.0 if seq == 0 else 4.0)
        pump.warm()
        codec = PacketCodec()
        ready = []  # 84 frames of 32 packets: 64 would fill a batch
        for k in range(84):
            scratch = np.zeros((VEC, rings.rx.snap), np.uint8)
            frames = [make_frame(CLIENT_IP, SERVER_IP, proto=17,
                                 sport=20000 + k, dport=1000 + j)
                      for j in range(32)]
            cols, m = codec.parse(frames, a, scratch)
            ready.append(({c: v.copy() for c, v in cols.items()}, m,
                          scratch))
        sent, refill = [0], threading.Event()

        def push(n):
            for cols, m, scratch in ready[sent[0]:sent[0] + n]:
                assert rings.rx.push(cols, m, payload=scratch)
            sent[0] += n

        def push_whole(n):  # no take sees a part of the n frames
            with pump._held_lock:
                push(n)

        release = rings.rx.release

        def release_and_refill():  # on the pump's writer thread
            release()
            if refill.is_set():
                push(1)

        rings.rx.release = release_and_refill

        def wait_for(cond, timeout=5):
            deadline = time.monotonic() + timeout
            while not cond() and time.monotonic() < deadline:
                time.sleep(0.001)
            return cond()

        push(20)
        pump.start()
        try:
            for held in (20, 40, 60):  # batches of 20, 20 and 20
                assert wait_for(lambda: len(pump._taken) == held)
                if held < 60:
                    push_whole(20 if held == 20 else 24)
            # the ring is full, the hold cap spent
            assert wait_for(lambda: pump.stats["batches"] == 3)
            refill.set()  # the first batch's 20 slots, refilled
            got = drain(rings, 20)
            assert wait_for(lambda: sent[0] == 84)
            refill.clear()
            assert wait_for(lambda: pump.stats["batches"] == 4, 2)
            assert pump.stats["inflight"] == 3
            got += drain(rings, 64)
            assert [int(s[0]) for s, _d, _i, _n in got] == \
                [20000 + k for k in range(84)]
            assert pump.stats["batch_errors"] == 0
        finally:
            assert pump.stop()
            rings.close()


class TestDispatchShutdown:
    def test_stop_under_load_never_hangs(self):
        """stop() while batches are dispatched and the (single) fetch
        worker is wedged: the stop sentinel can land AHEAD of a batch
        the dispatcher was still handing off, and the worker exits on
        the sentinel without processing it — the tx writer must rescue
        the stranded batch instead of spinning on its seq forever
        (every thread joins; the default unbounded join relies on it)."""
        dp, a, _b = make_forwarding_dp()
        rings = IORingPair(n_slots=64)
        try:
            for cycle in range(3):
                push_frames(rings, a, 12, per=64)
                pump = DataplanePump(dp, rings, max_batch=VEC,
                                     fetch_workers=1, max_inflight=2,
                                     fetch_delay=0.05)
                if cycle == 0:
                    pump.warm()
                pump.start()
                # stop at a different pipeline fill each cycle
                time.sleep(0.05 + cycle * 0.1)
                assert pump.stop(join_timeout=30), \
                    "pump threads did not join under load"
                # whatever was dispatched must be accounted: written
                # frames + error batches, never a silently stuck seq
                while rings.tx.peek() is not None:
                    rings.tx.release()
        finally:
            rings.close()


class TestAdaptiveChain:
    def _run(self, chain_k, n_frames=24, per=64):
        dp, a, _b = make_forwarding_dp()
        rings = IORingPair(n_slots=64)
        push_frames(rings, a, n_frames, per)
        # max_batch=2·VEC: 24×64 pkts of backlog is three full buckets,
        # so the chainer (when armed) must fold
        pump = DataplanePump(dp, rings, max_batch=2 * VEC,
                             chain_k=chain_k)
        pump.warm()
        pump.start()
        try:
            got = drain(rings, n_frames)
            stats = dict(pump.stats)
        finally:
            assert pump.stop()
            rings.close()
        return got, stats

    def test_chain_and_overlap_modes_identical_results(self):
        plain, s0 = self._run(chain_k=0)
        chained, s1 = self._run(chain_k=4)
        assert s0["chain_batches"] == 0
        assert s1["chain_batches"] >= 1 and s1["chain_k_peak"] >= 2
        # fewer device dispatches for the same traffic — that's the
        # whole point of the fold
        assert s1["batches"] < s0["batches"]
        assert len(plain) == len(chained)
        for (sa, da, ia, na), (sb, db, ib, nb) in zip(plain, chained):
            assert na == nb
            assert (sa == sb).all()
            assert (da == db).all()
            assert (ia == ib).all()

    @pytest.mark.slow  # ~15 s: adaptive-threshold behavior under light load; the chain==overlap bit-exact identity stays the fast anchor
    def test_light_load_never_pays_the_chain(self):
        """A single pending frame dispatches alone at the VEC bucket —
        the chainer only folds BACKLOG (its latency cost must not leak
        into the uncongested path)."""
        dp, a, _b = make_forwarding_dp()
        rings = IORingPair(n_slots=16)
        pump = DataplanePump(dp, rings, max_batch=4 * VEC, chain_k=4)
        pump.warm()
        pump.start()
        try:
            codec = PacketCodec()
            scratch = np.zeros((VEC, rings.rx.snap), np.uint8)
            for k in range(3):
                push_frames(rings, a, 1, per=4, codec=codec,
                            scratch=scratch)
                assert len(drain(rings, 1)) == 1  # one at a time
            assert pump.stats["chain_batches"] == 0
            assert pump.stats["batches"] == 3
        finally:
            assert pump.stop()
            rings.close()


class TestPersistentShutdown:
    @pytest.mark.parametrize("seed_frames", [0, 10])
    def test_stop_joins_cleanly_under_load(self, seed_frames):
        """stop() while frames are mid-flight between the refill queue
        and the tx writer: every thread must exit (the ADVICE r5 race
        left the writer spinning on an orphaned seq forever), and
        every batch the dispatcher COUNTED must reach the writer."""
        dp, a, _b = make_forwarding_dp()
        rings = IORingPair(n_slots=32)
        if seed_frames:
            push_frames(rings, a, seed_frames, per=4)
        pump = DataplanePump(dp, rings, mode="persistent",
                             max_inflight=4)
        pump.warm()
        pump.start()
        try:
            if seed_frames:
                # stop mid-load: at least one frame through, the rest
                # anywhere in the refill/collect/write stages
                deadline = time.monotonic() + 120
                while (pump.stats["frames"] == 0
                       and time.monotonic() < deadline):
                    time.sleep(0.005)
                assert pump.stats["frames"] > 0
            assert pump.stop(join_timeout=60), \
                "persistent pump threads did not join"
            # no orphaned seq: everything dispatched was written or
            # accounted as an error, never silently dropped
            assert (pump.stats["frames"] + pump.stats["batch_errors"]
                    >= pump.stats["batches"] - pump.max_inflight)
        finally:
            rings.close()

    @pytest.mark.slow  # ~13 s: shutdown with resident frames; orderly persistent-pump shutdown is covered fast in test_io
    def test_stop_with_frames_resident_in_device_rings(self):
        """stop() while whole windows are still in flight on the
        device rings (ISSUE 7): every thread joins, the steady state
        made zero host callbacks, and every offered packet is either
        written, attributably dropped, or still resident in the rx
        ring — nothing vanishes silently."""
        dp, a, _b = make_forwarding_dp()
        rings = IORingPair(n_slots=64)
        n_frames, per = 30, 32
        push_frames(rings, a, n_frames, per)
        pump = DataplanePump(dp, rings, mode="persistent",
                             max_inflight=2, ring_slots=2,
                             ring_windows=2)
        pump.warm()
        pump.start()
        try:
            deadline = time.monotonic() + 120
            while (pump.stats["frames"] == 0
                   and time.monotonic() < deadline):
                time.sleep(0.002)
            assert pump.stats["frames"] > 0
            assert pump.stop(join_timeout=60), \
                "pump threads did not join with windows in flight"
            s = pump.stats
            assert s["io_callbacks"] == 0
            assert s["ring_windows"] >= 1
            assert s["batch_errors"] == 0
            # count packets still resident in the rx ring (includes
            # held frames abandoned by stop — those are the shutdown
            # drops)
            remaining, k = 0, 0
            while True:
                f = rings.rx.peek_nth(k)
                if f is None:
                    break
                remaining += f.n
                k += 1
            offered = n_frames * per
            assert s["pkts"] + s["drops_tx_stall"] + remaining \
                == offered
            assert s["drops_shutdown"] <= remaining
        finally:
            rings.close()

    def test_repeated_stop_start_cycles(self):
        """The dispatch-done gate must reset per pump instance — churn
        a few persistent pumps over the same rings under load."""
        dp, a, _b = make_forwarding_dp()
        rings = IORingPair(n_slots=32)
        try:
            for cycle in range(2):
                push_frames(rings, a, 4, per=4)
                pump = DataplanePump(dp, rings, mode="persistent")
                pump.warm()
                pump.start()
                got = drain(rings, 4)
                assert len(got) == 4
                assert pump.stop(join_timeout=60)
        finally:
            rings.close()
