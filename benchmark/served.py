"""The served packet path under load: frames pushed into the agent's
in-process ``IORingPair``, served by ``DataplanePump`` (dispatch ladder,
fetch, in-order tx writer) at the configuration's ``IOConfig``, and
drained from the tx ring here. The load generator stands in for the IO
daemon; no veth, AF_PACKET or NIC is in the window.

One thread (the caller's) pushes and drains, so the load comes from one
process with few threads: the pump's own threads plus this one.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, List, Optional

import numpy as np

from benchmark.gen import FILL_BASE, Generator, hash64

# columns kept from sampled tx frames for the reference check
KEEP_COLS = ("src_ip", "dst_ip", "proto", "sport", "dport", "ttl", "rx_if",
             "disp", "next_hop", "meta")
# columns kept from the window's last frames, whose flows the check
# probes after the window (benchmark/check.py flow_probe)
TAIL_COLS = ("dst_ip", "dport", "disp")
# frame ids of the probe frames served after the window
PROBE_BASE = 1 << 30
POLL_S = 50e-6
PUSH_BURST = 8


class Feed:
    """Frames of a generator, built a chunk at a time and parsed by the
    program's codec exactly as the IO daemon parses received frames."""

    def __init__(self, gen: Generator, chunk: int = 32):
        from vpp_tpu.native.pktio import PacketCodec

        self.gen = gen
        self.chunk = chunk
        self.codec = PacketCodec(snap=gen.frame_bytes)
        self.fp = gen.frame_pkts
        self.lens = np.full(self.fp, gen.frame_bytes, np.uint32)
        self.c0 = -1
        self.rows = None
        self.rx_if = None

    def frame(self, k: int):
        """(cols, n, payload rows) of frame ``k``; ``meta`` carries k."""
        if not (0 <= k - self.c0 < self.chunk) or self.rows is None:
            self.c0 = k
            f = self.gen.frame_fields(np.arange(k, k + self.chunk))
            self.rows = self.gen.wire(f)
            self.rx_if = f["rx_if"]
        j = (k - self.c0) * self.fp
        return self._parse(self.rows[j:j + self.fp],
                           self.rx_if[j:j + self.fp], k)

    def build(self, f: Dict[str, np.ndarray], k: int, tcp_flags: int):
        """(cols, n, payload rows) of a frame of the header fields
        ``f`` (at most ``frame_pkts`` packets), with id ``k``."""
        return self._parse(self.gen.wire(f, tcp_flags), f["rx_if"], k)

    def _parse(self, rows, rx_if, k: int):
        n = len(rows)
        cols, n = self.codec.parse_inplace(rows, self.lens[:n], n, 0)
        cols["rx_if"][:n] = rx_if
        cols["meta"][:n] = k
        return cols, n, rows


class ServedPath:
    def __init__(self, dp, io_cfg, world: Dict):
        from vpp_tpu.io.pump import DataplanePump
        from vpp_tpu.io.rings import IORingPair

        self.dp = dp
        self.io = io_cfg
        self.rings = IORingPair(n_slots=io_cfg.n_slots, snap=io_cfg.snap)
        self.pump = DataplanePump(
            dp, self.rings,
            max_batch=io_cfg.max_batch, depth=io_cfg.depth,
            workers=io_cfg.workers, max_inflight=io_cfg.max_inflight,
            fetch_workers=io_cfg.fetch_workers, chain_k=io_cfg.chain_k,
            mode=io_cfg.pump_mode, ring_slots=io_cfg.io_ring_slots,
            ring_windows=io_cfg.io_ring_windows,
            ring_fault_limit=io_cfg.io_ring_fault_limit,
            tenant_quantum=io_cfg.io_tenant_quantum,
            icmp_src_ip=(int(world["gateway"]) if io_cfg.icmp_errors
                         else 0))
        self.started = False

    def warm(self) -> list:
        return self.pump.warm()

    def fill_sessions(self, gen: Generator, n_pkts: int) -> int:
        """Open ``n_pkts`` new flows through the pump's own chained
        program (the shape the saturated ladder dispatches), so the
        session table sits at its steady churn occupancy before the
        window. Runs before the pump starts: one committer at a time."""
        import jax

        from vpp_tpu.pipeline.dataplane import (
            PACKED_IN_ROWS,
            pack_packet_columns,
        )

        k = max(1, self.pump.chain_k)
        b = self.pump.max_batch
        per = k * b
        done, last = 0, None
        while done < n_pkts:
            f = gen.fields(np.arange(FILL_BASE + done, FILL_BASE + done + per,
                                     dtype=np.uint64))
            f["flags"] = np.ones(per, np.int32)
            flat = np.zeros((k, PACKED_IN_ROWS, b), np.int32)
            for j in range(k):
                cols = {c: v[j * b:(j + 1) * b] for c, v in f.items()}
                pack_packet_columns(flat[j].view(np.uint32), cols, b)
            if k > 1:
                last = self.dp.process_packed_chain(
                    flat, with_aux=True, stamps_us=np.zeros(k, np.int32))
            else:
                last = self.dp.process_packed(flat[0], with_aux=True)
            done += per
            if done % (per * 16) == 0:
                jax.block_until_ready(last)
        jax.block_until_ready(last)
        return done

    def start(self) -> None:
        self.pump.start()
        self.started = True

    def stop(self) -> None:
        if self.started:
            self.pump.stop()
            self.started = False

    def close(self) -> None:
        self.stop()
        self.rings.close()


class Load:
    """Push/drain loop over one ServedPath. Records, per frame id, the
    push time, the drain time and the packet count, and keeps the tx
    columns of the frames the check samples."""

    def __init__(self, path: ServedPath, feed: Feed, keep: set,
                 annotate: bool = False, tail: int = 0):
        self.path = path
        self.feed = feed
        self.keep = keep
        self.annotate = annotate
        self.pushed: Dict[int, tuple] = {}      # k -> (t_push, n)
        self.got: Dict[int, tuple] = {}         # k -> (t_drain, n)
        self.kept: Dict[int, Dict[str, np.ndarray]] = {}
        # (k, TAIL_COLS) of the last ``tail`` frames that came back
        self.tail = deque(maxlen=tail)
        # probe frames served after the window: k -> kept columns
        self.probe_pushed: Dict[int, int] = {}
        self.probe_got: Dict[int, Dict[str, np.ndarray]] = {}
        self.stray = 0
        self.refused: List[int] = []
        self.next_k = 0
        self._pending = None

    def _span(self, name):
        if self.annotate:
            import jax

            return jax.profiler.TraceAnnotation(name)
        return _NULL

    def drain(self) -> int:
        tx = self.path.rings.tx
        got = 0
        with self._span("load.drain"):
            while True:
                f = tx.peek()
                if f is None:
                    break
                t = time.perf_counter()
                n = f.n
                k = int(f.cols["meta"][0]) if n else -1
                if k in self.pushed and k not in self.got:
                    self.got[k] = (t, n)
                    if k in self.keep:
                        self.kept[k] = {c: f.cols[c][:n].copy()
                                        for c in KEEP_COLS}
                    if self.tail.maxlen:
                        self.tail.append((k, {c: f.cols[c][:n].copy()
                                              for c in TAIL_COLS}))
                elif k in self.probe_pushed and k not in self.probe_got:
                    self.probe_got[k] = {c: f.cols[c][:n].copy()
                                         for c in KEEP_COLS}
                else:
                    self.stray += 1
                tx.release()
                got += n
        return got

    def push_one(self, k: int, now: Optional[float] = None) -> bool:
        if self._pending is None or self._pending[0] != k:
            self._pending = (k,) + self.feed.frame(k)
        _, cols, n, rows = self._pending
        if not self.path.rings.rx.push(cols, n, payload=rows):
            return False
        self.pushed[k] = (time.perf_counter() if now is None else now, n)
        self._pending = None
        return True

    def saturate(self, t_end: float) -> None:
        """Refill the rx ring whenever it has room, until ``t_end``."""
        while True:
            now = time.perf_counter()
            if now >= t_end:
                return
            pushed = 0
            with self._span("load.push"):
                while pushed < PUSH_BURST and self.push_one(self.next_k):
                    self.next_k += 1
                    pushed += 1
            if not self.drain() and not pushed:
                time.sleep(POLL_S)

    def paced(self, due: np.ndarray, k0: int, t_end: float) -> None:
        """Push frame ``k0 + i`` at ``due[i]`` (absolute perf_counter
        seconds). A frame the full rx ring refuses at its due time is
        not retried: it is counted in ``refused``."""
        i = 0
        while True:
            now = time.perf_counter()
            if now >= t_end:
                break
            with self._span("load.push"):
                while i < len(due) and due[i] <= now:
                    k = k0 + i
                    if not self.push_one(k, now=float(due[i])):
                        self.refused.append(k)
                        self._pending = None
                    i += 1
                    now = time.perf_counter()
            self.drain()
            nxt = due[i] if i < len(due) else t_end
            wait = min(nxt, t_end) - time.perf_counter()
            if wait > 0:
                time.sleep(min(wait, POLL_S))
        self.next_k = k0 + i

    def serve_fields(self, f: Dict[str, np.ndarray], tcp_flags: int,
                     timeout_s: float = 60.0) -> List[int]:
        """Serve packets with header fields ``f`` after the window, in
        frames of the mix's size, and wait for them; returns the frame
        ids, in order, whose columns land in ``probe_got``."""
        fp = self.feed.fp
        ids = []
        t_end = time.perf_counter() + timeout_s
        for s in range(0, len(f["src_ip"]), fp):
            k = PROBE_BASE + len(self.probe_pushed)
            cols, n, rows = self.feed.build(
                {c: v[s:s + fp] for c, v in f.items()}, k, tcp_flags)
            while not self.path.rings.rx.push(cols, n, payload=rows):
                if time.perf_counter() > t_end:
                    return ids
                if not self.drain():
                    time.sleep(POLL_S)
            self.probe_pushed[k] = n
            ids.append(k)
        while time.perf_counter() < t_end and any(
                k not in self.probe_got for k in ids):
            if not self.drain():
                time.sleep(1e-3)
        return ids

    def finish(self, timeout_s: float = 60.0) -> int:
        """Drain until every pushed frame came back or ``timeout_s``
        passed; returns the frames still missing."""
        t_end = time.perf_counter() + timeout_s
        while time.perf_counter() < t_end:
            self.drain()
            if len(self.got) >= len(self.pushed):
                break
            time.sleep(1e-3)
        return len(self.pushed) - len(self.got)


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


_NULL = _Null()


def keep_set(seed_key: int, every: int, upto: int) -> set:
    """Frame ids whose tx columns the check keeps: a seeded 1-in-``every``
    draw over ids below ``upto``."""
    ids = np.arange(upto, dtype=np.uint64)
    pick = (hash64(seed_key, ids, 97) % np.uint64(every)) == 0
    return set(np.nonzero(pick)[0].tolist())


class TraceSlice:
    """Profiler trace of one slice of the window, started and stopped
    on a thread of its own so the load loop never stalls on it."""

    def __init__(self, logdir: str, start_at: float, length_s: float):
        self.logdir = logdir
        self.start_at = start_at
        self.length_s = length_s
        self.t0 = self.t1 = None
        self.error = None
        self._th = threading.Thread(target=self._run, daemon=True,
                                    name="bench-trace")

    def _run(self):
        import jax

        try:
            time.sleep(max(0.0, self.start_at - time.perf_counter()))
            jax.profiler.start_trace(self.logdir)
            self.t0 = time.perf_counter()
            time.sleep(self.length_s)
            self.t1 = time.perf_counter()
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001 — reported by the caller
            self.error = e

    def start(self):
        self._th.start()

    def join(self, timeout=None):
        self._th.join(timeout)
