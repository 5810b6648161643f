"""The one traffic generator. A traffic mix is a data file under
``benchmark/traffic/``; this module turns it, a configuration's address
plan (the ``world`` a system module returns) and ``--seed`` into frames.

Every packet is a pure function of ``(seed, packet index)``: frame ``k``
holds packets ``k * frame_pkts ... + frame_pkts - 1``, so the reference
regenerates any frame it checks without keeping it, and the same seed
gives the same inputs. Packets are built as real Ethernet/IPv4 bytes and
parsed by the program's own codec, as the IO daemon does before it
pushes a frame into the rx ring.

Mix keys (all numbers, no code):

- ``frame_pkts``: packets per frame; ``frame_bytes``: bytes per packet
  on the wire without the FCS; ``proto``: ``tcp`` (SYN) or ``udp``.
- ``flows``: 0 makes every packet the first of a new connection, its
  5-tuple unique in the run; N > 0 draws each packet uniformly from N
  fixed flows.
- ``src``: ``outside`` (the configuration's external source blocks, in
  on the uplink) or ``local_pod`` (in on the source pod's interface).
- ``dst_mix``: shares of ``local_pod``, ``peer_pod`` and ``vip``.
- ``dport``: per destination kind ``[base, span]``; the VIP takes its
  service port.
- ``arrival``: ``saturate`` (refill whenever the rx ring has room) or
  ``poisson`` at ``rate_fps`` frames per second.
"""

from __future__ import annotations

import math
from typing import Dict, Optional

import numpy as np

ETH = 14
IP4 = 20
PROTO_NUM = {"tcp": 6, "udp": 17}
L4_HDR = {"tcp": 20, "udp": 8}
SPORT_BASE = 1024
SPORT_SPAN = 65536 - SPORT_BASE
# packet index where the session-fill flows start; frames use [0, FILL_BASE)
FILL_BASE = 1 << 33

def _mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64 finaliser over uint64 (wraps mod 2**64)."""
    x = x.astype(np.uint64, copy=True)
    with np.errstate(over="ignore"):
        x ^= x >> np.uint64(30)
        x *= np.uint64(0xBF58476D1CE4E5B9)
        x ^= x >> np.uint64(27)
        x *= np.uint64(0x94D049BB133111EB)
        x ^= x >> np.uint64(31)
    return x


def seed_key(seed: int) -> int:
    """A 64-bit key from any whole-number seed (negative or > 2**63)."""
    return int(_mix64(np.array([seed & 0xFFFFFFFFFFFFFFFF], np.uint64))[0])


def hash64(key: int, idx: np.ndarray, salt: int) -> np.ndarray:
    """A 64-bit hash of each index under ``key``, one stream per salt."""
    with np.errstate(over="ignore"):
        x = (np.asarray(idx, np.uint64) * np.uint64(0x9E3779B97F4A7C15)
             + np.uint64((key ^ (salt * 0xD6E8FEB86659FD93))
                         & 0xFFFFFFFFFFFFFFFF))
    return _mix64(x)


def _uniform(key: int, idx: np.ndarray, salt: int) -> np.ndarray:
    """Uniform [0, 1) doubles, one per index."""
    return (hash64(key, idx, salt) >> np.uint64(11)).astype(np.float64) \
        * (1.0 / (1 << 53))


def _below(key: int, idx: np.ndarray, salt: int, n: int) -> np.ndarray:
    return (hash64(key, idx, salt) % np.uint64(n)).astype(np.int64)


def _affine(key: int, space: int) -> tuple:
    """(a, c) of a permutation x = (a*g + c) mod space: a odd-ish and
    coprime with ``space``, below 2**29 so a*g fits 64 bits for g < 2**34."""
    a = (key >> 3) % (1 << 28) * 2 + 1
    while math.gcd(a, space) != 1:
        a += 2
    return a, (key >> 17) % space


def _csum(words: np.ndarray) -> np.ndarray:
    """Internet checksum over rows of big-endian 16-bit words."""
    s = words.astype(np.uint64).sum(axis=1)
    for _ in range(3):
        s = (s & np.uint64(0xFFFF)) + (s >> np.uint64(16))
    return (~s.astype(np.uint32)) & np.uint32(0xFFFF)


class Generator:
    """Frames of one traffic mix over one configuration's address plan."""

    def __init__(self, mix: Dict, world: Dict, seed: int):
        self.mix = mix
        self.world = world
        self.seed = int(seed)
        self.key = seed_key(self.seed)
        self.frame_pkts = int(mix["frame_pkts"])
        self.proto = mix["proto"]
        self.frame_bytes = int(mix["frame_bytes"])
        need = ETH + IP4 + L4_HDR[self.proto]
        if self.frame_bytes < need:
            raise ValueError(f"frame_bytes {self.frame_bytes} < {need}")
        self.flows = int(mix.get("flows", 0))
        mixes = mix["dst_mix"]
        kinds = ("local_pod", "peer_pod", "vip")
        unknown = set(mixes) - set(kinds)
        if unknown:
            raise ValueError(f"unknown dst_mix kinds {sorted(unknown)}")
        self.cum = np.cumsum([float(mixes.get(k, 0.0)) for k in kinds])
        if abs(self.cum[-1] - 1.0) > 1e-9:
            raise ValueError(f"dst_mix shares sum to {self.cum[-1]}")
        self.pod_ip = np.asarray(world["pod_ip"], np.uint32)
        self.pod_if = np.asarray(world["pod_if"], np.int32)
        if mix["src"] == "outside":
            blocks = world["outside_blocks"]
            self.src_space = int(blocks["count"]) * int(blocks["hosts"])
        elif mix["src"] == "local_pod":
            self.src_space = len(self.pod_ip)
        else:
            raise ValueError(f"unknown src {mix['src']!r}")
        self.a, self.c = _affine(self.key, self.src_space * SPORT_SPAN)

    # --- header fields -------------------------------------------------
    def _src(self, sidx: np.ndarray) -> tuple:
        """(src ip, rx_if) of source-space indices."""
        if self.mix["src"] == "outside":
            b = self.world["outside_blocks"]
            hosts = int(b["hosts"])
            block = sidx % int(b["count"])
            host = sidx // int(b["count"]) % hosts + 1
            ip = (np.uint64(b["base"]) + (block.astype(np.uint64) << 8)
                  + host.astype(np.uint64))
            return ip.astype(np.uint32), np.full(len(sidx),
                                                 self.world["uplink_if"],
                                                 np.int32)
        return self.pod_ip[sidx], self.pod_if[sidx]

    def fields(self, g: np.ndarray) -> Dict[str, np.ndarray]:
        """Header fields of packets ``g`` (uint64 packet indices), as
        the rx ring columns carry them, plus ``kind`` (0 local pod,
        1 peer pod, 2 VIP) and ``src_pod`` (-1 from outside)."""
        g = np.asarray(g, np.uint64)
        key = self.key
        n = len(g)
        if self.flows:
            u = _uniform(key, g, 1)
            ident = np.minimum(u * self.flows, self.flows - 1).astype(
                np.uint64)
            sidx = _below(key, ident, 2, self.src_space)
            sport = SPORT_BASE + (ident % np.uint64(SPORT_SPAN)).astype(
                np.int64)
        else:
            ident = g
            with np.errstate(over="ignore"):
                x = (g * np.uint64(self.a) + np.uint64(self.c)) \
                    % np.uint64(self.src_space * SPORT_SPAN)
            sidx = (x % np.uint64(self.src_space)).astype(np.int64)
            sport = SPORT_BASE + (x // np.uint64(self.src_space)).astype(
                np.int64)
        src, rx_if = self._src(sidx)
        kind = np.searchsorted(self.cum, _uniform(key, ident, 3),
                               side="right").clip(0, 2)
        npods = len(self.pod_ip)
        if self.mix["src"] == "local_pod":
            # never to the sending pod itself
            dpod = (sidx + 1 + _below(key, ident, 4, npods - 1)) % npods
        else:
            dpod = _below(key, ident, 4, npods)
        peers = np.asarray(self.world.get("peer_nodes") or [0], np.int64)
        node = peers[_below(key, ident, 5, len(peers))]
        peer_ip = (np.uint64(self.world.get("node_net_base", 0))
                   + (node.astype(np.uint64) << np.uint64(8))
                   + (_below(key, ident, 6, 254) + 1).astype(np.uint64))
        vip_ip, vip_port = self.world.get("vip", (0, 0))
        dst = np.where(kind == 0, self.pod_ip[dpod].astype(np.uint64),
                       np.where(kind == 1, peer_ip, np.uint64(vip_ip)))
        dport = np.zeros(n, np.int64)
        for k, name in enumerate(("local_pod", "peer_pod")):
            base, span = self.mix["dport"].get(name, (0, 1))
            sel = kind == k
            dport[sel] = base + _below(key, ident[sel], 7 + k, span)
        dport[kind == 2] = vip_port
        return {
            "src_ip": src.astype(np.uint32),
            "dst_ip": dst.astype(np.uint32),
            "proto": np.full(n, PROTO_NUM[self.proto], np.int32),
            "sport": sport.astype(np.int32),
            "dport": dport.astype(np.int32),
            "ttl": np.full(n, 64, np.int32),
            "pkt_len": np.full(n, self.frame_bytes - ETH, np.int32),
            "rx_if": rx_if.astype(np.int32),
            "kind": kind.astype(np.int32),
            "src_pod": (sidx if self.mix["src"] == "local_pod"
                        else np.full(n, -1)).astype(np.int32),
        }

    def frame_fields(self, k: np.ndarray) -> Dict[str, np.ndarray]:
        """Fields of whole frames ``k``, [len(k) * frame_pkts]."""
        k = np.asarray(k, np.uint64)
        g = (k[:, None] * np.uint64(self.frame_pkts)
             + np.arange(self.frame_pkts, dtype=np.uint64)[None, :])
        return self.fields(g.ravel())

    # --- wire bytes ----------------------------------------------------
    def wire(self, f: Dict[str, np.ndarray],
             tcp_flags: int = 0x02) -> np.ndarray:
        """Ethernet/IPv4/TCP (a SYN unless ``tcp_flags`` says otherwise)
        or UDP frames with valid checksums, [n, frame_bytes] uint8."""
        n = len(f["src_ip"])
        fb = self.frame_bytes
        out = np.zeros((n, fb + (fb & 1)), np.uint8)
        out[:, 0:6] = (2, 0, 0, 0, 0, 2)
        out[:, 6:12] = (2, 0, 0, 0, 0, 1)
        out[:, 12:14] = (8, 0)
        ip = out[:, ETH:ETH + IP4]
        be = lambda a, w: a.astype(f">u{w}").view(np.uint8).reshape(n, w)  # noqa: E731
        ip[:, 0] = 0x45
        ip[:, 2:4] = be(np.full(n, fb - ETH), 2)
        ip[:, 4:6] = (0, 1)
        ip[:, 6:8] = (0x40, 0)
        ip[:, 8] = 64
        ip[:, 9] = PROTO_NUM[self.proto]
        ip[:, 12:16] = be(f["src_ip"], 4)
        ip[:, 16:20] = be(f["dst_ip"], 4)
        ip[:, 10:12] = be(_csum(ip.copy().view(">u2")), 2)
        l4o = ETH + IP4
        l4 = out[:, l4o:fb + (fb & 1)]
        l4len = fb - l4o
        l4[:, 0:2] = be(f["sport"], 2)
        l4[:, 2:4] = be(f["dport"], 2)
        if self.proto == "tcp":
            l4[:, 4:8] = (0, 0, 0, 1)
            l4[:, 12] = 5 << 4
            l4[:, 13] = tcp_flags
            l4[:, 14:16] = (0x20, 0)
            l4[:, 20:l4len] = ord("x")
            ck_at = 16
        else:
            l4[:, 4:6] = be(np.full(n, l4len), 2)
            ck_at = 6
        pseudo = np.zeros((n, 12), np.uint8)
        pseudo[:, 0:8] = out[:, ETH + 12:ETH + 20]
        pseudo[:, 9] = PROTO_NUM[self.proto]
        pseudo[:, 10:12] = be(np.full(n, l4len), 2)
        words = np.concatenate([pseudo, l4], axis=1).view(">u2")
        ck = _csum(words)
        ck[ck == 0] = 0xFFFF
        l4[:, ck_at:ck_at + 2] = be(ck, 2)
        return np.ascontiguousarray(out[:, :fb])

    # --- arrivals --------------------------------------------------------
    def due_times(self, seconds: float) -> Optional[np.ndarray]:
        """Open-loop frame due times in [0, seconds), or None when the
        mix saturates. Drawn from the seed like the packets."""
        if self.mix["arrival"] == "saturate":
            return None
        if self.mix["arrival"] != "poisson":
            raise ValueError(f"unknown arrival {self.mix['arrival']!r}")
        rate = float(self.mix["rate_fps"])
        # every seed gets the same exponential gaps (the n quantiles of
        # the distribution), in its own order: seeds change the order of
        # the arrivals, not how many or how bursty they are
        n = int(np.ceil(rate * seconds))
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
        order = np.argsort(hash64(self.key, np.arange(n, dtype=np.uint64), 11),
                           kind="stable")
        t = np.cumsum(gaps[order])
        return t[t < seconds]
