"""MXU bit-plane ACL classify: 5-tuple first-match as a bf16 matmul.

The dense VPU classify (vpp_tpu.ops.acl) compares every packet against
every rule field-by-field — O(P*R) vector ops that leave the MXU idle.
This module re-expresses the match as a matrix multiply so the systolic
array does the heavy lifting, the TPU-native answer to VPP's hand-tuned
C classifier (acl-plugin-fa, SURVEY.md §2.3):

For one header bit ``b`` and a rule with mask bit ``m`` and value bit
``v``, the masked-equality mismatch is ``m * (b XOR v)``; since
``b XOR v = b + v - 2bv`` for bits, it linearizes to
``b * m(1-2v) + m*v``. Summing over all 104 header bit-planes
(src 32, dst 32, proto 8, sport 16, dport 16):

    mismatches(p, r) = bits[p, :] @ coeff[:, r] + k[r]

with ``coeff = m*(1-2v)`` in {-1, 0, 1} and ``k[r] = sum(m*v)``. A rule
matches iff its mismatch count is exactly zero. Sums are <= 104, so
bf16 operands with f32 accumulation are exact. First-match-wins is a
min-reduction over matching rule indices, fused into the matmul epilogue
in VMEM (the [P, R] mismatch matrix never reaches HBM).

Applicability: address prefixes, exact protocols and exact-or-wildcard
ports all linearize. A true port *range* (lo < hi, not 0..65535) does
not; the table compiler reports ``ok=False`` and the caller keeps the
dense path for that table (k8s NetworkPolicy rules are always
exact-port, so the 10k-rule north-star regime is MXU-served).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from vpp_tpu.ops._pallas import get_pallas, out_struct
from vpp_tpu.ops.acl import AclVerdict, assemble_global_verdict
from vpp_tpu.pipeline.vector import PacketVector

# Bit-plane layout: [src 0:32 | dst 32:64 | proto 64:72 | sport 72:88 |
# dport 88:104 | zero-pad 104:128]. 128 planes align with the MXU edge.
PLANES = 128
_SRC0, _DST0, _PROTO0, _SPORT0, _DPORT0 = 0, 32, 64, 72, 88

# Encoded "no rule matched" sentinel (any valid index is < R <= 2**20).
ENC_MISS = np.int32(0x7FFFFFF)

# Packet-tile and rule-tile sizes for the fused kernel.
_PT = 256
_RT = 1024


class MxuTable(NamedTuple):
    """Host-compiled bit-plane form of one rule table."""

    coeff: np.ndarray  # [PLANES, R'] float32 in {-1, 0, 1}
    k: np.ndarray      # [R'] float32, per-rule mismatch constant
    act: np.ndarray    # [R'] int32 action per COLUMN (-1 padding) — the
                       # column-aligned action table. The dense glb_action
                       # rows and the bit-plane columns shard into
                       # *different* block boundaries when R' > R, so a
                       # rule-sharded classify must look the deny bit up
                       # in column space, not row space.
    ok: bool           # False => table has range rules; use dense path


def mxu_rule_capacity(max_rules: int) -> int:
    """Padded rule count R' for a table of ``max_rules``: a multiple of
    the rule tile so the kernel grid divides evenly."""
    if max_rules <= _RT:
        return max_rules
    return ((max_rules + _RT - 1) // _RT) * _RT


def empty_bitplanes(max_rules: int) -> MxuTable:
    """The compiled form of an empty table: no plane can ever match."""
    r_cap = mxu_rule_capacity(max_rules)
    return MxuTable(
        coeff=np.zeros((PLANES, r_cap), np.float32),
        k=np.ones(r_cap, np.float32),
        act=np.full(r_cap, -1, np.int32),
        ok=True,
    )


def _compile_columns(packed: dict, n: int):
    """The bit-plane math for ``n`` rule rows (any subset): returns
    (coeff [PLANES, n], k [n], bad [n]). Live-ness comes from
    action != -1, so padding rows compile to never-match columns
    regardless of position."""
    coeff = np.zeros((PLANES, n), np.float32)
    k = np.ones(n, np.float32)  # default: never matches
    live = packed["action"] != -1

    def put_field(base: int, nbits: int, value, mask):
        """Fill coefficient planes [base, base+nbits) for all live rules
        in one vectorized [nbits, R] block (a Python loop here was the
        dominant cost of a 10k-rule commit — VERDICT r2 Weak #4)."""
        shifts = np.arange(nbits, dtype=np.uint32)[:, None]
        m = ((mask[None, :] >> shifts) & 1).astype(np.float32)
        v = ((value[None, :] >> shifts) & 1).astype(np.float32)
        coeff[base:base + nbits, :] = np.where(
            live[None, :], m * (1.0 - 2.0 * v), 0.0
        )
        k[:] += np.where(live[None, :], m * v, 0.0).sum(axis=0)

    k[:] = np.where(live, 0.0, 1.0)
    src_net = packed["src_net"].astype(np.uint32)
    src_mask = packed["src_mask"].astype(np.uint32)
    dst_net = packed["dst_net"].astype(np.uint32)
    dst_mask = packed["dst_mask"].astype(np.uint32)
    put_field(_SRC0, 32, src_net, src_mask)
    put_field(_DST0, 32, dst_net, dst_mask)

    proto = packed["proto"]
    proto_any = proto < 0  # -1 any (padding rows are dead via k=1 anyway)
    put_field(
        _PROTO0, 8,
        np.where(proto_any, 0, proto).astype(np.uint32),
        np.where(proto_any, 0, 0xFF).astype(np.uint32),
    )

    bad_rows = np.zeros(n, bool)
    for base, lo_key, hi_key in (
        (_SPORT0, "sport_lo", "sport_hi"),
        (_DPORT0, "dport_lo", "dport_hi"),
    ):
        lo, hi = packed[lo_key], packed[hi_key]
        exact = lo == hi
        anyp = (lo == 0) & (hi == 65535)
        bad_rows |= live & ~exact & ~anyp
        put_field(
            base, 16,
            np.where(exact, lo, 0).astype(np.uint32),
            np.where(exact, 0xFFFF, 0).astype(np.uint32),
        )
    # Fail closed: a range-port rule can never match in the MXU planes —
    # zero its coefficient column AND pin k=1 so the mismatch count is a
    # constant 1 regardless of packet bits. A caller that ignores
    # ok=False misses the rule rather than wildcarding its ports.
    coeff[:, :] = np.where(bad_rows[None, :], 0.0, coeff)
    k[:] = np.where(bad_rows, 1.0, k)
    return coeff, k, bad_rows


def compile_bitplanes_full(packed: dict, max_rules: int):
    """Compile pack_rules() output into bit-plane coefficients.

    ``packed`` holds [R] arrays: src_net/src_mask/dst_net/dst_mask/
    proto/sport_lo/sport_hi/dport_lo/dport_hi/action (action == -1 marks
    padding rows). Padding and non-compilable rows get k=1 so they can
    never produce a zero mismatch count. Returns (MxuTable, bad [R]) —
    ``bad`` is the per-row non-compilable mask the incremental update
    threads forward."""
    r_cap = mxu_rule_capacity(max_rules)
    n = len(packed["action"])
    cblock, kblock, bad = _compile_columns(packed, n)
    coeff = np.zeros((PLANES, r_cap), np.float32)
    k = np.ones(r_cap, np.float32)
    coeff[:, :n] = cblock
    k[:n] = kblock
    act = np.full(r_cap, -1, np.int32)
    act[:n] = packed["action"]
    return MxuTable(coeff=coeff, k=k, act=act, ok=not bad.any()), bad


def compile_bitplanes(packed: dict, max_rules: int) -> MxuTable:
    return compile_bitplanes_full(packed, max_rules)[0]


def compile_bitplanes_update(packed: dict, max_rules: int,
                             prev: MxuTable, prev_bad: np.ndarray,
                             changed: np.ndarray):
    """Incremental recompile: only the ``changed`` rule columns are
    recomputed; every other column is carried over from ``prev``
    (policy churn touches ~one policy's worth of rows out of 10k —
    recompiling the whole [PLANES, R'] matrix per commit was the
    dominant host cost of the commit path, VERDICT r4 Next #3).
    Returns (MxuTable, bad) exactly as compile_bitplanes_full would
    have produced from scratch — equivalence-tested in
    tests/test_acl_mxu.py."""
    coeff = prev.coeff.copy()
    k = prev.k.copy()
    act = prev.act.copy()
    bad = prev_bad.copy()
    if len(changed):
        sub = {key: arr[changed] for key, arr in packed.items()}
        cblock, kblock, bsub = _compile_columns(sub, len(changed))
        coeff[:, changed] = cblock
        k[changed] = kblock
        act[changed] = packed["action"][changed]
        bad[changed] = bsub
    return MxuTable(coeff=coeff, k=k, act=act, ok=not bad.any()), bad


def packet_bit_planes(pkts: PacketVector) -> jnp.ndarray:
    """Explode packet headers into the [P, PLANES] bf16 bit matrix."""

    def bits(field, base, nbits, out):
        shifts = jnp.arange(nbits, dtype=jnp.uint32)[None, :]
        b = (field.astype(jnp.uint32)[:, None] >> shifts) & 1
        return out.at[:, base : base + nbits].set(b.astype(jnp.bfloat16))

    p = pkts.src_ip.shape[0]
    out = jnp.zeros((p, PLANES), jnp.bfloat16)
    out = bits(pkts.src_ip, _SRC0, 32, out)
    out = bits(pkts.dst_ip, _DST0, 32, out)
    out = bits(pkts.proto, _PROTO0, 8, out)
    out = bits(pkts.sport, _SPORT0, 16, out)
    out = bits(pkts.dport, _DPORT0, 16, out)
    return out


def _classify_kernel(bits_ref, coeff_ref, k_ref, enc_ref):
    """One (packet-tile, rule-tile) step: matmul + fused first-match min.

    Grid = (P/_PT, R/_RT); the enc output block depends only on the
    packet tile, so rule tiles revisit it sequentially and accumulate
    the running min (TPU grids iterate the last axis innermost).
    """
    pl, _pltpu = get_pallas("mxu_first_match")
    j = pl.program_id(1)
    mism = jnp.dot(
        bits_ref[:], coeff_ref[:], preferred_element_type=jnp.float32
    )
    mism = mism + k_ref[:]  # [PT, RT] + [1, RT]
    rt = mism.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, mism.shape, 1) + j * rt
    enc = jnp.where(mism == 0.0, col, ENC_MISS)
    tile_min = jnp.min(enc, axis=1, keepdims=True)  # [PT, 1]

    @pl.when(j == 0)
    def _():
        enc_ref[:] = tile_min

    @pl.when(j > 0)
    def _():
        enc_ref[:] = jnp.minimum(enc_ref[:], tile_min)


@functools.partial(jax.jit, static_argnames=("interpret",))
def mxu_first_match(
    bits: jnp.ndarray,
    coeff: jnp.ndarray,
    k: jnp.ndarray,
    interpret: bool = False,
) -> jnp.ndarray:
    """Encoded first-match over the bit-plane table.

    bits [P, PLANES] bf16, coeff [PLANES, R] bf16, k [R] f32 →
    enc [P] int32: matched rule index, ENC_MISS when nothing matched.
    P and R are padded to tile multiples here; callers pass any size.
    """
    # lazy import (ISSUE 16 satellite): the Pallas modules load only
    # when this kernel actually traces — never on a CPU run that
    # serves the reference rung
    pl, pltpu = get_pallas("mxu_first_match")
    p = bits.shape[0]
    r = coeff.shape[1]
    pt = min(_PT, max(8, p))
    p_pad = ((p + pt - 1) // pt) * pt
    rt = min(_RT, r)
    r_pad = ((r + rt - 1) // rt) * rt
    if p_pad != p:
        bits = jnp.pad(bits, ((0, p_pad - p), (0, 0)))
    if r_pad != r:
        coeff = jnp.pad(coeff, ((0, 0), (0, r_pad - r)))
        k = jnp.pad(k, (0, r_pad - r), constant_values=1.0)

    enc = pl.pallas_call(
        _classify_kernel,
        grid=(p_pad // pt, r_pad // rt),
        in_specs=[
            pl.BlockSpec((pt, PLANES), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((PLANES, rt), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, rt), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((pt, 1), lambda i, j: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=out_struct((p_pad, 1), jnp.int32, bits, coeff, k),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=2 * p_pad * PLANES * r_pad,
            bytes_accessed=p_pad * PLANES * 2 + PLANES * r_pad * 2 + p_pad * 4,
            transcendentals=0,
        ),
    )(bits, coeff.astype(jnp.bfloat16), k[None, :])
    return enc[:p, 0]


def mxu_first_match_reference(
    bits: jnp.ndarray, coeff: jnp.ndarray, k: jnp.ndarray
) -> jnp.ndarray:
    """Pure-jnp equivalent of mxu_first_match (CPU mesh / cross-check)."""
    mism = (
        jnp.dot(bits, coeff.astype(jnp.bfloat16),
                preferred_element_type=jnp.float32)
        + k[None, :]
    )
    col = jax.lax.broadcasted_iota(jnp.int32, mism.shape, 1)
    return jnp.min(jnp.where(mism == 0.0, col, ENC_MISS), axis=1)


def mxu_classify_columns(tables, pkts: PacketVector) -> jnp.ndarray:
    """First-match COLUMN index of each packet against the bit-plane
    table (ENC_MISS = no match): packet-header bit explode + the
    backend dispatch (Pallas kernel on TPU, jnp reference elsewhere).
    The single entry point shared by the single-node classify below and
    the rule-sharded cluster classify
    (parallel/cluster.sharded_global_classify_mxu), so backend dispatch
    can never diverge between them."""
    from vpp_tpu.ops._pallas import use_pallas

    bits = packet_bit_planes(pkts)
    if use_pallas():
        return mxu_first_match(bits, tables.glb_mxu_coeff, tables.glb_mxu_k)
    return mxu_first_match_reference(
        bits, tables.glb_mxu_coeff, tables.glb_mxu_k
    )


def acl_classify_global_mxu(tables, pkts: PacketVector) -> AclVerdict:
    """Drop-in replacement for acl_classify_global using the MXU path.

    Requires tables compiled with bit-planes (glb_mxu_coeff/glb_mxu_k in
    DataplaneTables) and a table with no range rules (builder keeps the
    dense path otherwise).
    """
    enc = mxu_classify_columns(tables, pkts)
    matched = enc != ENC_MISS
    safe = jnp.where(matched, enc, 0)
    act = tables.glb_action[safe]
    return assemble_global_verdict(tables, pkts, matched, act == 1, enc)
