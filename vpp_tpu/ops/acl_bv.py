"""Bit-vector (BV) ACL classify: interval bitmaps + word-AND first-match.

The Lucent bit-vector scheme (Lakshman/Stiliadis; the hierarchical
per-dimension decomposition hyperscale gateways use — Gryphon,
PAPERS.md) as the third global-classify implementation next to the
dense VPU compare (vpp_tpu.ops.acl) and the MXU bit-plane matmul
(vpp_tpu.ops.acl_mxu) — and, unlike MXU, extended to the per-interface
local tables.

Commit time (host/numpy, composed with the identity-diff incremental
pack in pipeline/tables.py): every rule constrains each of the 5
header dimensions to an *interval* — a CIDR prefix is the contiguous
range [net, net | ~mask], a port range is [lo, hi] — so per dimension
the distinct interval boundaries split the value space into at most
2R+1 segments. For each segment we precompute the set of rules whose
interval covers it, packed as a rule bitmap of ``ceil(R/32)`` uint32
words: the [I, W] interval→bitmap matrix. Protocol is an 8-bit field,
so it gets a small direct [256, W] table with wildcard (proto == -1)
rules folded into every row.

Device time, per packet: 5 segment lookups (4 × ``jnp.searchsorted``
binary searches + 1 direct proto index), 5 bitmap-row gathers, 4
word-ANDs, and a first-set-bit priority encode (argmax over nonzero
words, then a popcount bit isolate) — O(W + log I) per packet instead
of the dense path's O(R) per packet. At the 10k-rule regime that is
~320 words of AND against 10,240 rule compares × 9 field ops: an
order of magnitude less arithmetic, on the CPU backend (where the MXU
matmul path has no systolic array to win on) as well as on TPU.

Memory: ~5 × 2R × R/32 uint32 words (~105 MB at 10,240 rules) — the
``classifier: auto`` selection honors ``classifier_bv_mem_mb`` before
allocating (pipeline/tables.py). The verdict fold reuses
``assemble_global_verdict`` / the local-verdict semantics of
vpp_tpu.ops.acl, so deny/permit/unmatched-default stays in lockstep
with the dense oracle by construction. On the multi-chip mesh the
structure shards along the rule-WORD axis (the boundary arrays stay
replicated — a segment's bitmap covers ALL rules, but the row packs
them into words, and the WORD axis divides): each shard ANDs its word
block, first-set-bits locally, and one encoded pmin recombines —
parallel/cluster.py ``sharded_global_classify_bv`` via the
partition-rule layer (docs/PARTITIONING.md, docs/CLASSIFIER.md).
"""

from __future__ import annotations

import functools
import time
from typing import Dict, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from vpp_tpu.ops.acl import (
    AclVerdict,
    acl_unmatched_default,
    assemble_global_verdict,
)
from vpp_tpu.pipeline.vector import PacketVector

# Direct-table rows of the protocol plane (8-bit IANA proto space).
PROTO_ROWS = 256

# Interval dimensions in (name, boundary dtype, max value) order; the
# proto plane is direct-indexed and handled separately.
_ADDR_MAX = (1 << 32) - 1
_PORT_MAX = 65535
DIMS: Tuple[str, ...] = ("src", "dst", "sport", "dport")
_DIM_MAX = {"src": _ADDR_MAX, "dst": _ADDR_MAX,
            "sport": _PORT_MAX, "dport": _PORT_MAX}
# boundary-array pad values (>= every real value, so searchsorted of a
# real value never lands past the live prefix before the clip)
_DIM_PAD = {"src": _ADDR_MAX, "dst": _ADDR_MAX,
            "sport": 0x7FFFFFFF, "dport": 0x7FFFFFFF}
_DIM_DTYPE = {"src": np.uint32, "dst": np.uint32,
              "sport": np.int32, "dport": np.int32}


def bv_capacity(max_rules: int, enabled: bool = True) -> Tuple[int, int, int]:
    """(interval rows, bitmap words, proto rows) for a table of
    ``max_rules``. Shapes are compile-time (epoch-invariant), so a
    disabled classifier collapses to minimal placeholder shapes — the
    BV kernels are then never selected, only the pytree fields exist."""
    if not enabled:
        return 2, 1, 2
    return 2 * max_rules + 2, max(1, (max_rules + 31) // 32), PROTO_ROWS


def bv_global_bytes(max_rules: int) -> int:
    """Device bytes of one fully-enabled BV structure: 4 interval
    bitmap matrices + the proto plane + the boundary/count arrays —
    the memory formula ``classifier: auto``'s cap gates on."""
    ib, w, pr = bv_capacity(max_rules, True)
    return ib * w * 4 * 4 + pr * w * 4 + ib * 4 * 4 + 4 * 4


def bv_config_bytes(config) -> int:
    """Device bytes of every BV structure a config allocates: the global
    table's and ``max_tables`` local tables' at ``max_rules``."""
    return (bv_global_bytes(config.max_global_rules)
            + int(config.max_tables) * bv_global_bytes(config.max_rules))


def bv_enabled_for(config) -> bool:
    """Whether this config allocates (and commit-time builds) the BV
    structure: explicit ``classifier: bv`` always (``pallas`` rides
    the SAME planes — ISSUE 16); ``auto`` only when the worst-case
    structures, the global one and the ``max_tables`` local ones, fit
    the ``classifier_bv_mem_mb`` cap."""
    knob = getattr(config, "classifier", "auto")
    if knob in ("bv", "pallas"):
        return True
    if knob != "auto":
        return False
    cap_mb = int(getattr(config, "classifier_bv_mem_mb", 256))
    return bv_config_bytes(config) <= cap_mb * (1 << 20)


class BvTable(NamedTuple):
    """Host-compiled interval-bitmap form of one rule table."""

    bnd_src: np.ndarray    # uint32 [I] segment start points (pad: max)
    bnd_dst: np.ndarray    # uint32 [I]
    bnd_sport: np.ndarray  # int32 [I]
    bnd_dport: np.ndarray  # int32 [I]
    nbnd: np.ndarray       # int32 [4] live boundary count per dimension
    bm_src: np.ndarray     # uint32 [I, W] segment -> rule bitmap
    bm_dst: np.ndarray     # uint32 [I, W]
    bm_sport: np.ndarray   # uint32 [I, W]
    bm_dport: np.ndarray   # uint32 [I, W]
    bm_proto: np.ndarray   # uint32 [PR, W] direct proto plane
    ok: bool               # False => a live rule has a non-prefix mask
    #                        (inexpressible as one interval); use the
    #                        dense path. Like MXU's ok=False, the bad
    #                        rule is excluded from the bitmaps, so a
    #                        caller that ignores ok misses the rule
    #                        rather than mismatching.
    build_ms: float        # host build cost of the LAST compile (only
    #                        the rebuilt dimension planes are paid)


def empty_bv(max_rules: int, enabled: bool = True) -> BvTable:
    """The compiled form of an empty table: one all-covering segment
    per dimension with no rule bit set — nothing ever matches."""
    ib, w, pr = bv_capacity(max_rules, enabled)
    out = {}
    for dim in DIMS:
        bnd = np.full(ib, _DIM_PAD[dim], _DIM_DTYPE[dim])
        bnd[0] = 0
        out[f"bnd_{dim}"] = bnd
        out[f"bm_{dim}"] = np.zeros((ib, w), np.uint32)
    return BvTable(
        nbnd=np.ones(4, np.int32),
        bm_proto=np.zeros((pr, w), np.uint32),
        ok=True, build_ms=0.0, **out,
    )


def _dim_columns(packed: Dict[str, np.ndarray], dim: str):
    """Per-rule (lo, hi, use, bad) interval columns of one dimension.

    ``use`` marks rules contributing an interval (live, non-empty);
    ``bad`` marks live rules whose constraint is NOT one interval (a
    non-prefix address mask) — they poison ``ok`` and are excluded.
    A pre-masked net with bits outside the mask can never match in the
    dense kernel either, so it is an EMPTY interval, not a bad one."""
    live = packed["action"] != -1
    if dim in ("src", "dst"):
        net = packed[f"{dim}_net"].astype(np.int64)
        mask = packed[f"{dim}_mask"].astype(np.int64)
        inv = (~mask) & _ADDR_MAX
        prefix_ok = ((inv + 1) & inv) == 0
        aligned = (net & mask) == net
        lo = net
        hi = net | inv
        bad = live & ~prefix_ok
        use = live & prefix_ok & aligned
    else:
        lo = np.clip(packed[f"{dim}_lo"].astype(np.int64), 0, _PORT_MAX)
        hi = np.clip(packed[f"{dim}_hi"].astype(np.int64), -1, _PORT_MAX)
        bad = np.zeros(len(lo), bool)
        use = live & (lo <= hi)
    return lo, hi, use, bad


def _build_plane(lo: np.ndarray, hi: np.ndarray, use: np.ndarray,
                 dim: str, cap_i: int, cap_w: int):
    """One dimension's (boundaries, live count, [I, W] bitmap)."""
    vmax = _DIM_MAX[dim]
    pts = np.concatenate([np.asarray([0], np.int64), lo[use], hi[use] + 1])
    pts = np.unique(pts[(pts >= 0) & (pts <= vmax)])
    n = len(pts)
    bnd = np.full(cap_i, _DIM_PAD[dim], _DIM_DTYPE[dim])
    bnd[:n] = pts.astype(bnd.dtype)
    bm = np.zeros((cap_i, cap_w), np.uint32)
    if use.any():
        # rule r covers segment rows [j0, j1): its interval contains
        # every boundary point in [lo, hi]
        j0 = np.searchsorted(pts, lo, side="left")
        j1 = np.searchsorted(pts, hi, side="right")
        nrules = len(lo)
        rows = np.arange(n)[:, None]
        for w in range(cap_w):
            r0, r1 = w * 32, min((w + 1) * 32, nrules)
            if r0 >= nrules or not use[r0:r1].any():
                continue
            cover = (use[None, r0:r1]
                     & (rows >= j0[None, r0:r1])
                     & (rows < j1[None, r0:r1]))
            bits = np.uint32(1) << np.arange(r1 - r0, dtype=np.uint32)
            bm[:n, w] = np.bitwise_or.reduce(
                np.where(cover, bits[None, :], np.uint32(0)), axis=1
            )
    return bnd, n, bm


def _build_proto_plane(proto: np.ndarray, live: np.ndarray,
                       cap_pr: int, cap_w: int) -> np.ndarray:
    """Direct [PR, W] proto plane with wildcard (-1) rules folded into
    every row. Padding rows (proto -2, action -1) set no bit."""
    bm = np.zeros((cap_pr, cap_w), np.uint32)
    nrules = len(proto)
    rows = np.arange(cap_pr)[:, None]
    for w in range(cap_w):
        r0, r1 = w * 32, min((w + 1) * 32, nrules)
        if r0 >= nrules or not live[r0:r1].any():
            continue
        p = proto[r0:r1].astype(np.int64)
        cover = live[None, r0:r1] & ((p[None, :] == -1) | (rows == p[None, :]))
        bits = np.uint32(1) << np.arange(r1 - r0, dtype=np.uint32)
        bm[:, w] = np.bitwise_or.reduce(
            np.where(cover, bits[None, :], np.uint32(0)), axis=1
        )
    return bm


def compile_bv(
    packed: Dict[str, np.ndarray],
    max_rules: int,
    prev: Optional[BvTable] = None,
    prev_cols: Optional[dict] = None,
) -> Tuple[BvTable, dict, Tuple[str, ...]]:
    """Compile pack_rules() output into the interval-bitmap structure.

    Incremental per DIMENSION plane: ``prev_cols`` caches every rule's
    interval columns from the last compile, so a commit that only
    churns ports (the gen-policy shape) rebuilds the sport/dport
    planes and carries src/dst/proto over untouched — composing with
    the identity-diff pack, which already made producing ``packed``
    cheap. A single boundary can shift every segment row, so a touched
    dimension rebuilds from scratch; untouched dimensions are free.

    Returns ``(table, cols, rebuilt)``: ``cols`` is the cache for the
    next call, ``rebuilt`` the dimension names recompiled this time
    (tests + ``show acl`` observability).
    """
    t0 = time.perf_counter()
    cap_i, cap_w, cap_pr = bv_capacity(max_rules, True)
    cols: dict = {}
    rebuilt = []
    out: dict = {}
    nbnd = np.ones(4, np.int32)
    bad_any = False
    for k, dim in enumerate(DIMS):
        lo, hi, use, bad = _dim_columns(packed, dim)
        bad_any = bad_any or bool(bad.any())
        cols[dim] = (lo, hi, use)
        reuse = (
            prev is not None and prev_cols is not None and dim in prev_cols
            and all(np.array_equal(a, b)
                    for a, b in zip(prev_cols[dim], cols[dim]))
        )
        if reuse:
            out[f"bnd_{dim}"] = getattr(prev, f"bnd_{dim}")
            out[f"bm_{dim}"] = getattr(prev, f"bm_{dim}")
            nbnd[k] = prev.nbnd[k]
        else:
            bnd, n, bm = _build_plane(lo, hi, use, dim, cap_i, cap_w)
            out[f"bnd_{dim}"] = bnd
            out[f"bm_{dim}"] = bm
            nbnd[k] = n
            rebuilt.append(dim)
    live = packed["action"] != -1
    cols["proto"] = (packed["proto"].copy(), live)
    if (prev is not None and prev_cols is not None and "proto" in prev_cols
            and all(np.array_equal(a, b)
                    for a, b in zip(prev_cols["proto"], cols["proto"]))):
        bm_proto = prev.bm_proto
    else:
        bm_proto = _build_proto_plane(packed["proto"], live, cap_pr, cap_w)
        rebuilt.append("proto")
    table = BvTable(
        nbnd=nbnd, bm_proto=bm_proto, ok=not bad_any,
        build_ms=(time.perf_counter() - t0) * 1e3, **out,
    )
    return table, cols, tuple(rebuilt)


# --- device kernels ---------------------------------------------------


def _first_set_bit(words: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """First-match over AND-combined rule bitmaps [P, W]: the lowest
    set bit across the word vector is the first (highest-priority)
    matching rule. argmax finds the first nonzero word; the isolated
    lowest bit's popcount(x-1) gives its in-word position exactly
    (integer-only — no float log tricks)."""
    nz = words != 0
    matched = jnp.any(nz, axis=1)
    widx = jnp.argmax(nz, axis=1).astype(jnp.int32)
    w = jnp.take_along_axis(words, widx[:, None], axis=1)[:, 0]
    low = w & (~w + jnp.uint32(1))
    bit = lax.population_count(low - jnp.uint32(1)).astype(jnp.int32)
    rule = widx * 32 + bit
    return matched, jnp.where(matched, rule, -1)


def _segment_of(bnd: jnp.ndarray, vals: jnp.ndarray, n) -> jnp.ndarray:
    """Segment row of each value: the boundary at-or-below it. Pads
    sort >= every real value; the clip covers the one value equal to
    the pad (address 255.255.255.255)."""
    i = jnp.searchsorted(bnd, vals, side="right").astype(jnp.int32) - 1
    return jnp.clip(i, 0, n - 1)


def bv_first_match(
    bnd_src, bnd_dst, bnd_sport, bnd_dport, nbnd,
    bm_src, bm_dst, bm_sport, bm_dport, bm_proto,
    pkts: PacketVector,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(matched [P] bool, rule_idx [P] int32, -1 = miss) over one BV
    table: 4 binary searches + 5 row gathers + 4 ANDs + the priority
    encode. Shared by the global classify and the differential tests."""
    si = _segment_of(bnd_src, pkts.src_ip, nbnd[0])
    di = _segment_of(bnd_dst, pkts.dst_ip, nbnd[1])
    pi = _segment_of(bnd_sport, pkts.sport, nbnd[2])
    qi = _segment_of(bnd_dport, pkts.dport, nbnd[3])
    pr = jnp.clip(pkts.proto, 0, bm_proto.shape[0] - 1)
    words = (bm_src[si] & bm_dst[di] & bm_sport[pi] & bm_dport[qi]
             & bm_proto[pr])
    return _first_set_bit(words)


def acl_classify_global_bv(tables, pkts: PacketVector) -> AclVerdict:
    """Drop-in replacement for acl_classify_global on the BV path.

    Requires tables compiled with interval bitmaps (glb_bv_* fields,
    builder ``bv_enabled``) and ok=True (no non-prefix masks — the
    selection keeps the dense path otherwise, like MXU's ok gate)."""
    matched, rule = bv_first_match(
        tables.glb_bv_bnd_src, tables.glb_bv_bnd_dst,
        tables.glb_bv_bnd_sport, tables.glb_bv_bnd_dport,
        tables.glb_bv_nbnd,
        tables.glb_bv_src, tables.glb_bv_dst,
        tables.glb_bv_sport, tables.glb_bv_dport, tables.glb_bv_proto,
        pkts,
    )
    safe = jnp.where(matched, rule, 0)
    act = tables.glb_action[safe]
    return assemble_global_verdict(tables, pkts, matched, act == 1, rule)


def _local_segment_of(bnd: jnp.ndarray, t: jnp.ndarray, vals: jnp.ndarray,
                      n: jnp.ndarray) -> jnp.ndarray:
    """``_segment_of`` of each packet in ITS table's boundaries: a
    vectorised binary search over the flattened [T * I] boundary array
    that gathers only the probed element per packet and step (log2(I)
    steps), where searching gathered [P, I] rows would read every
    packet's whole row. The live prefix [0, n) holds every boundary at
    or below a real value (pads sort above), so the count it gives,
    minus one and clipped, equals ``searchsorted`` over the whole row."""
    width = bnd.shape[1]
    flat = bnd.reshape(-1)
    dt = jnp.promote_types(bnd.dtype, vals.dtype)
    v = vals.astype(dt)
    base = t * width
    lo = jnp.zeros_like(n)
    hi = n
    for _ in range(max(1, int(width).bit_length())):
        live = lo < hi
        mid = (lo + hi) >> 1
        le = flat[base + jnp.where(live, mid, 0)].astype(dt) <= v
        lo = jnp.where(live & le, mid + 1, lo)
        hi = jnp.where(live & ~le, mid, hi)
    return jnp.clip(lo - 1, 0, n - 1)


def _local_rows(tables, pkts: PacketVector):
    """(table slot [P], has_table [P], the five bitmap rows [P, W]) of
    each packet's rx-interface local table."""
    tid = tables.if_local_table[pkts.rx_if]
    t = jnp.maximum(tid, 0)
    nb = tables.acl_bv_nbnd[t]  # [P, 4]
    si = _local_segment_of(tables.acl_bv_bnd_src, t, pkts.src_ip, nb[:, 0])
    di = _local_segment_of(tables.acl_bv_bnd_dst, t, pkts.dst_ip, nb[:, 1])
    pi = _local_segment_of(tables.acl_bv_bnd_sport, t, pkts.sport, nb[:, 2])
    qi = _local_segment_of(tables.acl_bv_bnd_dport, t, pkts.dport, nb[:, 3])
    pr = jnp.clip(pkts.proto, 0, tables.acl_bv_proto.shape[1] - 1)
    rows = (tables.acl_bv_src[t, si], tables.acl_bv_dst[t, di],
            tables.acl_bv_sport[t, pi], tables.acl_bv_dport[t, qi],
            tables.acl_bv_proto[t, pr])
    return t, tid >= 0, rows


def _local_verdict(tables, pkts: PacketVector, t, has_table, matched,
                   rule) -> AclVerdict:
    safe = jnp.where(matched, rule, 0)
    act = tables.acl_action[t, safe]
    permit = jnp.where(
        matched, act == 1, acl_unmatched_default(pkts, tables.acl_nrules[t])
    )
    return AclVerdict(
        permit=jnp.where(has_table, permit, True),
        rule_idx=jnp.where(has_table & matched, rule, -1),
    )


def acl_classify_local_bv(tables, pkts: PacketVector) -> AclVerdict:
    """acl_classify_local on the BV path: each packet looks up its rx
    interface's local table planes (a binary search per dimension in
    its own table's boundaries, then one bitmap row per dimension), so
    the whole frame still classifies in one dense op. Unlike the MXU
    path (global-only), this serves the per-interface tables too."""
    t, has_table, rows = _local_rows(tables, pkts)
    src, dst, sport, dport, proto = rows
    matched, rule = _first_set_bit(src & dst & sport & dport & proto)
    return _local_verdict(tables, pkts, t, has_table, matched, rule)


# --- pallas rung (ISSUE 16) -------------------------------------------
#
# The classifier ladder's "pallas" rung keeps the BV *structure* (the
# interval bitmaps are the right data layout) and replaces the hot
# reduction — today 5 row gathers land [P, W] word vectors in HBM,
# then 4 word-ANDs and the argmax/popcount priority encode each
# re-stream them — with ONE fused kernel: the five gathered rows tile
# into VMEM once and the AND + first-set-bit min-reduction never
# materializes the combined word matrix. The 4 binary searches and the
# row gathers stay XLA (log(I) scalar work per packet; the gather is
# the one op XLA already lowers well). Dispatch follows the acl_mxu.py
# precedent via ops/_pallas.py: compiled kernel on a TPU backend, the
# jnp rung (bv_first_match) everywhere else — bit-exact, and interpret
# mode keeps the differential suite runnable under JAX_PLATFORMS=cpu.

# Encoded "no rule matched" sentinel of the fused kernel (any valid
# rule index is < 32 * W <= 2**20 at the supported table sizes).
BV_ENC_MISS = np.int32(0x7FFFFFF)

# Packet-tile and word-tile sizes (the acl_mxu _PT/_RT analog).
_BV_PT = 256
_BV_WT = 512


def _bv_first_set_kernel(src_ref, dst_ref, sp_ref, dp_ref, pr_ref,
                         enc_ref):
    """One (packet-tile, word-tile) step: AND the five bitmap-row
    tiles, isolate each word's lowest set bit, and fold the running
    first-match min (grid iterates the word axis innermost, so the
    enc block accumulates across word tiles exactly like the MXU
    kernel's rule tiles)."""
    from vpp_tpu.ops._pallas import get_pallas

    pl, _pltpu = get_pallas("bv_first_set")
    j = pl.program_id(1)
    w = (src_ref[...] & dst_ref[...] & sp_ref[...] & dp_ref[...]
         & pr_ref[...])
    low = w & (~w + jnp.uint32(1))
    bit = lax.population_count(low - jnp.uint32(1)).astype(jnp.int32)
    wt = w.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1) + j * wt
    cand = jnp.where(w != jnp.uint32(0), col * 32 + bit, BV_ENC_MISS)
    tile_min = jnp.min(cand, axis=1, keepdims=True)  # [PT, 1]

    @pl.when(j == 0)
    def _():
        enc_ref[...] = tile_min

    @pl.when(j > 0)
    def _():
        enc_ref[...] = jnp.minimum(enc_ref[...], tile_min)



@functools.partial(jax.jit, static_argnames=("interpret",))
def bv_first_set(rows_src: jnp.ndarray, rows_dst: jnp.ndarray,
                 rows_sport: jnp.ndarray, rows_dport: jnp.ndarray,
                 rows_proto: jnp.ndarray,
                 interpret: bool = False) -> jnp.ndarray:
    """Fused word-AND + first-set-bit over five gathered bitmap rows.

    rows_* [P, W] uint32 → enc [P] int32: first (lowest-index) rule
    whose bit survives the AND, BV_ENC_MISS when none does. Bit-exact
    with ``_first_set_bit(rows AND-combined)`` — the differential
    suite (tests/test_pallas_kernels.py) holds the two together.
    P and W are padded to tile multiples here; zero pad words can
    never produce a candidate."""
    return _first_set_call("bv_first_set", interpret, rows_src, rows_dst,
                           rows_sport, rows_dport, rows_proto)


@functools.partial(jax.jit, static_argnames=("interpret",))
def acl_local_bv_first_set(rows_src: jnp.ndarray, rows_dst: jnp.ndarray,
                           rows_sport: jnp.ndarray, rows_dport: jnp.ndarray,
                           rows_proto: jnp.ndarray,
                           interpret: bool = False) -> jnp.ndarray:
    """``bv_first_set`` for the local tables' rows, under its own kernel
    name, so a device trace times the local classify apart from the
    global one."""
    return _first_set_call("acl_local_bv_first_set", interpret, rows_src,
                           rows_dst, rows_sport, rows_dport, rows_proto)


def _first_set_call(name: str, interpret: bool, rows_src, rows_dst,
                    rows_sport, rows_dport, rows_proto) -> jnp.ndarray:
    from vpp_tpu.ops._pallas import get_pallas

    pl, pltpu = get_pallas("bv_first_set")
    p, wn = rows_src.shape
    pt = min(_BV_PT, max(8, p))
    p_pad = ((p + pt - 1) // pt) * pt
    wt = min(_BV_WT, max(1, wn))
    w_pad = ((wn + wt - 1) // wt) * wt
    rows = [rows_src, rows_dst, rows_sport, rows_dport, rows_proto]
    if p_pad != p or w_pad != wn:
        rows = [jnp.pad(r, ((0, p_pad - p), (0, w_pad - wn)))
                for r in rows]

    spec = pl.BlockSpec((pt, wt), lambda i, j: (i, j),
                        memory_space=pltpu.VMEM)
    enc = pl.pallas_call(
        _bv_first_set_kernel,
        grid=(p_pad // pt, w_pad // wt),
        in_specs=[spec] * 5,
        out_specs=pl.BlockSpec((pt, 1), lambda i, j: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((p_pad, 1), jnp.int32),
        interpret=interpret,
        name=name,
        cost_estimate=pl.CostEstimate(
            flops=9 * p_pad * w_pad,
            bytes_accessed=5 * p_pad * w_pad * 4 + p_pad * 4,
            transcendentals=0,
        ),
    )(*rows)
    return enc[:p, 0]


def bv_first_match_fused(
    bnd_src, bnd_dst, bnd_sport, bnd_dport, nbnd,
    bm_src, bm_dst, bm_sport, bm_dport, bm_proto,
    pkts: PacketVector, interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``bv_first_match`` with the word-AND + priority encode running
    in the fused Pallas kernel (same signature + return contract:
    matched [P] bool, rule [P] int32 with -1 = miss)."""
    si = _segment_of(bnd_src, pkts.src_ip, nbnd[0])
    di = _segment_of(bnd_dst, pkts.dst_ip, nbnd[1])
    pi = _segment_of(bnd_sport, pkts.sport, nbnd[2])
    qi = _segment_of(bnd_dport, pkts.dport, nbnd[3])
    pr = jnp.clip(pkts.proto, 0, bm_proto.shape[0] - 1)
    enc = bv_first_set(bm_src[si], bm_dst[di], bm_sport[pi],
                       bm_dport[qi], bm_proto[pr], interpret=interpret)
    matched = enc != BV_ENC_MISS
    return matched, jnp.where(matched, enc, -1)


def _bv_global_first_match(tables, pkts: PacketVector, fused: bool):
    args = (
        tables.glb_bv_bnd_src, tables.glb_bv_bnd_dst,
        tables.glb_bv_bnd_sport, tables.glb_bv_bnd_dport,
        tables.glb_bv_nbnd,
        tables.glb_bv_src, tables.glb_bv_dst,
        tables.glb_bv_sport, tables.glb_bv_dport, tables.glb_bv_proto,
        pkts,
    )
    return bv_first_match_fused(*args) if fused else bv_first_match(*args)


def acl_classify_global_pallas(tables, pkts: PacketVector) -> AclVerdict:
    """The classifier ladder's "pallas" rung, global table: BV planes
    with the fused first-set kernel on a TPU backend, the jnp BV rung
    everywhere else (the mxu_classify_columns dispatch pattern — the
    CPU/fallback path is bit-exact by construction because it IS
    acl_classify_global_bv's math)."""
    from vpp_tpu.ops._pallas import use_pallas

    matched, rule = _bv_global_first_match(tables, pkts,
                                           fused=use_pallas())
    safe = jnp.where(matched, rule, 0)
    act = tables.glb_action[safe]
    return assemble_global_verdict(tables, pkts, matched, act == 1, rule)


def acl_classify_local_pallas(tables, pkts: PacketVector) -> AclVerdict:
    """The "pallas" rung's local classify: the per-packet searches and
    row gathers stay XLA, the word-AND + priority encode runs in the
    fused kernel, under its own name (``acl_local_bv_first_set``).
    Falls back to acl_classify_local_bv off-TPU — bit-exact (identical
    gathered rows, identical encode)."""
    from vpp_tpu.ops._pallas import use_pallas

    if not use_pallas():
        return acl_classify_local_bv(tables, pkts)
    t, has_table, rows = _local_rows(tables, pkts)
    enc = acl_local_bv_first_set(*rows)
    matched = enc != BV_ENC_MISS
    return _local_verdict(tables, pkts, t, has_table, matched,
                          jnp.where(matched, enc, -1))
