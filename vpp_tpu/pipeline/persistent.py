"""Persistent device pump over device-resident descriptor rings.

ISSUE 7 tentpole. The r6 persistent mode kept ONE resident
``lax.while_loop`` on the device but fed it through TWO ordered
``io_callback`` host round trips per frame (fetch + deliver) — each a
blocking device↔host synchronization, which is why BENCH_r05 measured
the daemon persistent path at 61.7% goodput with a 52 ms pump p99
while the same transport's transfer ceiling sat at 76.9 Mpps. nanoPU's
reflex-plane framing (PAPERS.md) is the latency model: the NIC-to-
compute path must not bounce through the host per frame.

This rework makes the steady state io_callback-free:

  * the host (stager thread) writes compacted ~20 B/packet descriptors
    into a pinned staging window (io/rings.py DeviceDescRing) and
    ships the WHOLE window with one transfer — the dispatch of the
    jitted window program (pipeline/dataplane.py ``_ring_call``);
  * on-device, a ``lax.while_loop`` polls the rx cursor against the
    shipped tail, runs the fused step per slot, and appends verdict
    descriptors + aux summaries to the device tx ring;
  * the tx ring rides back in the window's ONE result fetch (fetcher
    thread) — the aux-rider pattern generalized to the wire path — and
    with the double-buffered windows the fetch of window N overlaps
    the staging + dispatch of window N+1. The frame cursor and the
    session tables thread window-to-window as a device-resident carry,
    so per-frame accounting never costs a host sync.

Per frame in steady state: 1/S of a dispatch + 1/S of a fetch (S =
``io_ring_slots``), zero host callbacks — vs 2 blocking callbacks per
frame before. Window fill is adaptive: a lone frame dispatches in a
1-slot window (the latency floor is preserved), a backlog fills the
window before dispatch (throughput). The window program compiles ONCE
process-wide through the ``_jitted_step`` cache — an epoch-swap
restart of the pump re-uses the compiled program, where the r6 loop
paid a fresh per-instance jit every restart.

``stats["io_callbacks"]`` counts host callback invocations made by the
device program. The ring design makes none — the counter exists so a
regression reintroducing a callback into the steady state is a
measured fact, not prose.
"""

from __future__ import annotations

import queue
import threading
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from vpp_tpu.io.rings import DESC_ROWS, DeviceDescRing
from vpp_tpu.pipeline.dataplane import (
    PACKED_IN_ROWS,
    _jitted_step,
    count_device_transfer,
)
from vpp_tpu.testing import faults

assert DESC_ROWS == PACKED_IN_ROWS, (
    "io/rings.py DESC_ROWS must track pipeline.dataplane.PACKED_IN_ROWS"
)

STOP = np.int32(-1)  # legacy control word (kept for API compat)

_SENTINEL = object()


class PersistentPump:
    """Host side of the device-ring persistent path.

    API is unchanged from the r6 resident loop: ``submit()`` hands a
    [5, B] packed frame in, ``result_ex()`` yields ``(out, aux)`` per
    frame in submission order, ``stop()`` flushes everything in flight
    and returns the final session tables. Internally, submitted frames
    are staged into descriptor-ring windows and exchanged with the
    device one window at a time (module doc).

    ``fastpath``/``classifier``/``skip_local``/``sweep_stride``/
    ``ml_mode``/``ml_kind`` mirror
    the owning Dataplane's epoch selection exactly as before — the
    window program is fetched from the process-wide ``_jitted_step``
    cache keyed on them plus the ring geometry, so a pump restart
    (epoch swap) never recompiles.

    ``ring_slots`` frames per window and ``ring_windows`` staging
    buffers (>= 2: the double buffer that overlaps window N's
    writeback with window N+1's refill) are config-static shape —
    ``io.io_ring_slots`` / ``io.io_ring_windows``.
    """

    def __init__(self, tables, batch: int, max_frames: int = 1 << 20,
                 fastpath: bool = True, classifier: str = "dense",
                 skip_local: bool = False,
                 sweep_stride: Optional[int] = None,
                 ring_slots: int = 8, ring_windows: int = 2,
                 ml_mode: str = "off", ml_kind: str = "mlp",
                 tel_mode: str = "off", tnt_mode: str = "off",
                 sess_hash: str = "fwd"):
        self.batch = int(batch)
        self.fastpath_enabled = bool(fastpath)
        self.ring = DeviceDescRing(slots=ring_slots, batch=self.batch,
                                   windows=ring_windows)
        # latency-governor actuator (ISSUE 13; io/governor.py): the
        # stager closes a window once it holds this many slots, even
        # with more backlog queued — the host-side window-shaping
        # lever between the 1-slot lone-frame floor and the S-slot
        # backlog fill. Written by the owning pump's dispatch thread,
        # read by the stager: a plain int (GIL-atomic), no lock —
        # and NOT part of the window program's inputs beyond the
        # already-dynamic slot count `n`, so governing never retraces.
        self._fill_limit = self.ring.slots
        self._in: "queue.Queue" = queue.Queue()
        # dispatched windows awaiting their result fetch, in dispatch
        # order: (widx, n_frames, tx_ring, aux_ring) device futures
        self._fetch_q: "queue.Queue" = queue.Queue()
        self._out: "queue.Queue" = queue.Queue()
        self._tables0 = tables
        self._tables_pending = None
        self._tables_final = None
        # set by the owning DataplanePump (under ITS stats lock) once
        # this ring's counters have been folded into its accumulator —
        # a concurrent stats sync then must not count them again
        self.retired = False
        self._error: Optional[BaseException] = None
        self._threads: list = []
        self._max_frames = max_frames  # legacy knob; windows need no budget
        # telemetry gate (ops/telemetry.py; ISSUE 11): with it on, the
        # window program takes the per-slot stamp lane + dispatch
        # clock and returns the packed telemetry rider as a 5th output
        # riding the window's one result fetch
        self._tel = tel_mode
        self._step = _jitted_step(classifier, skip_local, fast=fastpath,
                                  form="ring", sweep_stride=sweep_stride,
                                  ring_slots=self.ring.slots,
                                  ml_mode=ml_mode, ml_kind=ml_kind,
                                  tel_mode=tel_mode, tnt_mode=tnt_mode,
                                  sess_hash=sess_hash)
        # device-resident frame cursor, threaded window-to-window next
        # to the tables (the sweep-cursor pattern); fetched only by
        # stats()/stop, never per window
        self._cursor0 = jnp.int32(0)
        # stager writes windows_dispatched, fetcher writes the rest —
        # one lock serializes the counters and the snapshot
        self._stats_lock = threading.Lock()
        self.stats = {
            # windows fully exchanged (dispatched AND written back)
            "ring_windows": 0,
            # frames staged through the ring (fill telemetry: frames
            # vs windows*slots is the window-fill ratio `show io`
            # derives)
            "ring_frames": 0,
            "windows_dispatched": 0,
            # priority-lane preemptions (ISSUE 13): windows the stager
            # shipped EARLY because a priority slot landed (the lane's
            # bounded-queueing mechanism — a reflex frame never waits
            # for the backlog to drain into its window). Folded into
            # the owning pump's ring accumulator across restarts.
            "priority_preempts": 0,
            # host callback invocations by the device program — the
            # ring steady state makes NONE (module doc). Any future
            # callback added to the window program MUST route its
            # host function through a counter bump here; the lowered-
            # program check (tests/test_device_rings.py
            # TestCallbackFreeProgram) is what actually catches a
            # callback sneaking in without one.
            "io_callbacks": 0,
        }
        # latest telemetry rider (fetcher-written under _stats_lock):
        # the raw int32 vector of pack_tel_rider, cumulative — the
        # owning pump unpacks it with the config geometry
        self._tel_last: Optional[np.ndarray] = None

    # --- lifecycle ---
    def start(self) -> "PersistentPump":
        for fn, name in ((self._stage_loop, "persistent-stage"),
                         (self._fetch_loop, "persistent-fetch")):
            t = threading.Thread(target=fn, daemon=True, name=name)
            t.start()
            self._threads.append(t)
        return self

    def _check_error(self) -> None:
        if self._error is not None:
            raise RuntimeError("persistent loop died") from self._error

    @property
    def failed(self) -> bool:
        """True once either ring thread has died. The owning pump's
        dispatch loop polls this between bursts so a death with no
        pending submit still counts toward the ring-fault fallback
        (a wedged ring must not hide behind an idle rx queue)."""
        return self._error is not None

    def submit(self, flat: np.ndarray, now: int,
               stamp_us: int = 0, priority: bool = False) -> None:
        """Queue one packed [5, B] frame; ``now`` is its per-slot
        timestamp (must be >= 0) and ``stamp_us`` its rx-enqueue
        microsecond stamp for the wire-latency histogram (0 =
        unstamped; ignored with telemetry off). ``priority`` marks a
        reflex-lane frame (ISSUE 13): the stager ships its window the
        moment the slot lands instead of draining the backlog into it.
        The frame is COPIED — callers may reuse their staging buffer
        immediately."""
        assert now >= 0
        self._check_error()
        self._in.put((int(now), int(stamp_us),
                      np.array(flat, np.int32, copy=True),
                      bool(priority)))

    def set_fill_limit(self, n_slots: int) -> None:
        """Governor actuator: cap the stager's window fill at
        ``n_slots`` (clamped to [1, ring slots]). Host-side only —
        the window program's slot count is already a dynamic input,
        so no jit variant is touched."""
        self._fill_limit = max(1, min(int(n_slots), self.ring.slots))

    def fill_avg(self, last: Optional[tuple] = None):
        """``(snapshot, avg_fill)`` where ``snapshot`` is the ring's
        cumulative ``(windows, slots)`` pair and ``avg_fill`` the
        average slots per window SINCE ``last`` (None until a window
        shipped in the delta) — the governor's occupancy input."""
        snap = self.ring.fill_snapshot()
        w0, s0 = last if last is not None else (0, 0)
        dw, ds = snap[0] - w0, snap[1] - s0
        return snap, (ds / dw if dw > 0 else None)

    def checkpoint_sessions(self, timeout: float = 30.0):
        """Consistent DEVICE COPY of the in-ring session state, taken
        by the stager BETWEEN windows (the ring threads its tables
        privately and donates them window-to-window, so an outside
        reader can neither see them nor safely hold a reference — a
        copy at a window boundary is the only coherent read). The
        crash-consistent snapshotter's freshness hook
        (io/pump.py sync_sessions): without it, a long-lived ring
        would leave dp.tables frozen at launch state and every
        interval snapshot would capture stale sessions against an
        advancing clock. Returns a {field: device array} dict of
        SESSION_FIELDS, or None when the ring is stopping/dead or the
        wait times out (callers skip the sync — no worse than the
        pre-hook behavior)."""
        if self._error is not None:
            return None
        ev = threading.Event()
        box: dict = {}
        self._in.put(("ckpt", ev, box))
        if not ev.wait(timeout):
            return None
        return box.get("sessions")

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        return self.result_ex(timeout=timeout)[0]

    def result_ex(self, timeout: Optional[float] = None):
        """Like result(), but returns ``(out, aux)`` where ``aux`` is
        the frame's [8] int32 summary
        ``[fastpath, rx, sess_hits, insert_fails, evictions,
        ml_scored, ml_flagged, ml_drops]`` (the pump's regime,
        session-pressure and ML-marking telemetry)."""
        try:
            return self._out.get(timeout=timeout)
        except queue.Empty:
            self._check_error()  # surface the REAL cause if the loop died
            raise

    def stats_snapshot(self) -> dict:
        """Consistent copy of the ring counters plus the live overlap
        occupancy (in-flight windows, writeback lag). Host scalars
        only — nothing crosses the device transport."""
        with self._stats_lock:
            s = dict(self.stats)
        s["ring_inflight"] = self.ring.in_flight()
        s["ring_lag"] = s.pop("windows_dispatched") - s["ring_windows"]
        return s

    def tel_raw(self) -> Optional[np.ndarray]:
        """Latest telemetry rider (raw ``pack_tel_rider`` int32
        vector; cumulative) — None until the first telemetry-on window
        wrote back. The owning pump unpacks it against the config
        geometry (ops/telemetry.py unpack_tel_rider)."""
        with self._stats_lock:
            tel = self._tel_last
        return None if tel is None else tel.copy()

    def stop(self, join_timeout: float = 60.0):
        """Flush every queued frame through the device and return the
        final session tables."""
        self._in.put(None)
        for t in self._threads:
            t.join(timeout=join_timeout)
            if t.is_alive():
                raise RuntimeError("persistent loop did not exit")
        self._check_error()
        if self._tables_pending is not None:
            self._tables_final = jax.block_until_ready(
                self._tables_pending)
            self._tables_pending = None
        return self._tables_final

    @staticmethod
    def _is_ckpt(item) -> bool:
        return (isinstance(item, tuple) and len(item) == 3
                and item[0] == "ckpt")

    @staticmethod
    def _serve_ckpt(item, tables) -> None:
        """Fulfil one checkpoint_sessions request against the current
        between-windows carry (its buffers are live until the next
        dispatch donates them — the copy must land before that)."""
        from vpp_tpu.pipeline.tables import SESSION_FIELDS

        _, ev, box = item
        box["sessions"] = {f: jnp.copy(getattr(tables, f))
                           for f in SESSION_FIELDS}
        ev.set()

    # --- stager: refill queue -> staged windows -> device dispatch ---
    def _stage_loop(self) -> None:
        # the window program donates its whole carry (tables + cursor),
        # so the pump must OWN the buffers it threads: copy the
        # dataplane's live tables once here — the first window's
        # donation must not invalidate arrays the collector/CLI/
        # expire_sessions still read off dp.tables (they see the
        # pre-loop state until stop() grafts sessions back, exactly
        # the r6 in-loop-carry staleness contract)
        tables = jax.tree_util.tree_map(jnp.copy, self._tables0)
        cursor = self._cursor0
        try:
            stopping = False
            while not stopping:
                item = self._in.get()
                # session checkpoints at the window boundary: served
                # against the current carry, whose buffers are valid
                # exactly here (the next dispatch donates them)
                while self._is_ckpt(item):
                    self._serve_ckpt(item, tables)
                    item = self._in.get()
                if item is None:
                    break
                # a free window, or None while the fetch side is wedged
                # — poll so a fetcher death can't deadlock the stager
                while True:
                    got = self.ring.acquire(timeout=0.2)
                    if got is not None:
                        break
                    if self._error is not None:
                        return
                widx, desc, nows, stamps = got
                n = 0
                pending_ckpt = None
                preempted = False
                # adaptive fill: drain whatever is already queued up to
                # the window size (capped by the governor's fill
                # limit), never wait for more — a lone frame ships in
                # a 1-slot window (latency floor), a backlog fills the
                # window (throughput). A PRIORITY slot ships the
                # window immediately (ISSUE 13): the reflex lane's
                # bounded queueing comes from never draining backlog
                # into a window a priority frame is already in.
                limit = min(self.ring.slots, self._fill_limit)
                while True:
                    now, stamp_us, flat, pri = item
                    desc[n] = flat
                    nows[n] = now
                    stamps[n] = stamp_us
                    n += 1
                    if pri:
                        # a preempt is a window shipped early ONLY
                        # when backlog was actually waiting to fill it
                        # — a lone priority frame on an idle queue
                        # ships the same 1-slot window either way
                        preempted = self._in.qsize() > 0
                        break
                    if n >= limit:
                        break
                    try:
                        item = self._in.get_nowait()
                    except queue.Empty:
                        break
                    if item is None:
                        stopping = True
                        break
                    if self._is_ckpt(item):
                        # close the window here; the request is served
                        # below against the POST-window carry (also a
                        # window boundary — still a consistent copy)
                        pending_ckpt = item
                        break
                # ONE async dispatch ships the window; the tx ring +
                # aux ride back in the fetcher's one result fetch.
                # faults: "ring.dispatch" stands in for a device
                # transfer error here — it kills this stager exactly
                # like a real dispatch failure, which is what arms the
                # pump's ring→dispatch degraded fallback
                faults.fire("ring.dispatch")
                if self._tel != "off":
                    from vpp_tpu.ops.telemetry import tel_clock_us

                    # per-packet wire latency is computed ON DEVICE at
                    # tx-append: the window ships the per-slot stamp
                    # lane + this dispatch clock, and the histogram
                    # bins ride back in the ONE result fetch below —
                    # no callback enters the program for telemetry
                    tables, cursor, tx_ring, aux_ring, tel = \
                        self._step(tables, cursor, desc, nows, stamps,
                                   np.int32(tel_clock_us()),
                                   np.int32(n))
                else:
                    tables, cursor, tx_ring, aux_ring = self._step(
                        tables, cursor, desc, nows, np.int32(n))
                    tel = None
                self.ring.note_fill(n)
                with self._stats_lock:
                    self.stats["windows_dispatched"] += 1
                    if preempted:
                        self.stats["priority_preempts"] += 1
                self._fetch_q.put((widx, n, tx_ring, aux_ring, tel))
                if pending_ckpt is not None:
                    self._serve_ckpt(pending_ckpt, tables)
            self._tables_pending = tables
        except BaseException as e:  # noqa: BLE001 — re-raised to the
            # caller from result()/stop(); a silently dead pump would
            # leave result() blocking to timeout
            self._error = e
        finally:
            if self._tables_pending is None and self._error is None:
                self._tables_pending = tables
            self._fetch_q.put(_SENTINEL)
            # unblock checkpoint requesters stranded behind the stop
            # sentinel (or a stager death): their wait would otherwise
            # run to its timeout for nothing
            while True:
                try:
                    item = self._in.get_nowait()
                except queue.Empty:
                    break
                if self._is_ckpt(item):
                    item[1].set()  # no "sessions" key = declined

    # --- fetcher: one result fetch per window, per-frame hand-off ---
    def _fetch_loop(self) -> None:
        try:
            while True:
                item = self._fetch_q.get()
                if item is _SENTINEL:
                    return
                widx, n, tx_ring, aux_ring, tel = item
                # the window's ONE device->host transfer: tx
                # descriptors + per-slot aux summaries + (telemetry
                # on) the packed telemetry rider, together
                # (faults: "ring.fetch" = the transfer failing)
                faults.fire("ring.fetch")
                if tel is not None:
                    out_h, aux_h, tel_h = jax.device_get(
                        (tx_ring, aux_ring, tel))
                    count_device_transfer("ring.window",
                                          (out_h, aux_h, tel_h))
                    with self._stats_lock:
                        self._tel_last = np.array(tel_h, np.int32)
                else:
                    out_h, aux_h = jax.device_get((tx_ring, aux_ring))
                    count_device_transfer("ring.window", (out_h, aux_h))
                out_h = np.asarray(out_h)
                aux_h = np.asarray(aux_h)
                # the staging buffer is reusable once its window's
                # exchange fully completed
                self.ring.release(widx)
                for i in range(n):
                    self._out.put((np.array(out_h[i]),
                                   np.array(aux_h[i])))
                with self._stats_lock:
                    self.stats["ring_windows"] += 1
                    self.stats["ring_frames"] += n
        except BaseException as e:  # noqa: BLE001 — surfaced via
            # _check_error exactly like a stager death
            if self._error is None:
                self._error = e
