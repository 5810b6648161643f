"""Million-route LPM: binary-search-over-prefix-lengths ip4-lookup.

The routing analogue of the BV classifier (ops/acl_bv.py; ISSUE 15):
instead of VPP's pointer-chasing mtrie — which a TPU cannot win on —
the FIB compiles into PER-PREFIX-LENGTH SORTED PREFIX PLANES and the
device lookup is one binary search per populated length:

    for L in populated lengths, longest first:
        m   = dst & mask(L)                       # constant mask
        i   = searchsorted(plane_L.prefixes, m)   # log2(N_L) compares
        hit = plane_L.prefixes[i] == m            # exact-match gather
        first hit wins (lengths walk longest -> shortest)

— the Waldvogel binary-search-on-prefix-lengths family, flattened for
a vector machine: every packet of the batch walks every populated
length (SPMD — no data-dependent early exit), so the cost is
O(P * lengths * log N) against the dense compare's O(P * F). At a
1M-route BGP feed with ~20 populated lengths that is ~400 fused
compare/gather lanes per packet versus 1,000,000 — and the dense
[P, F] hit matrix (8 GB at a 2048 batch) never materializes.

Shapes are CONFIG-static (the jit contract): each length's plane
capacity comes from ``dataplane.fib_lpm_plen_caps`` (default: every
length sized to ``fib_slots``), and a length whose cap is 0 gets a
zero-width plane the step factory SKIPS AT TRACE TIME — the
"config-static populated-length tuple" of ISSUE 15. Route churn never
retraces: only device VALUES (plane contents, counts) move per epoch.
A staged table that does not fit its planes (a length over its cap)
makes ``TableBuilder.lpm_ok()`` false and the selection ladder falls
back to dense — the BV ``ok=False`` degradation pattern, loudly
observable via ``show fib`` / ``vpp_tpu_fib_impl``.

Each plane is one ``[2, N_L]`` uint32 field of DataplaneTables
(``fib_lpm_p{L}``): row 0 the sorted masked prefixes (pad 0xFFFFFFFF
— sorts at/after every real value), row 1 the owning FIB slot. Route
DATA stays in the per-slot columns: both implementations resolve
through the ONE shared ``ops.fib.resolve_fib_slot`` (ECMP groups
included), so dense and LPM are bit-exact by construction. Keeping
planes per-length — separate pytree fields, not one [33, N] matrix —
is what makes route churn cheap: a BGP flap re-ships ONLY the touched
length's plane (+ the count vector and a small per-slot scatter blob),
every other plane keeps its device-array identity
(pipeline/tables.py ``_fib_dirty`` / ``_fib_incremental``).

Memory: sum over lengths of ``2 * cap_L * 4`` bytes (+ 132 B of
counts). The default per-length cap of ``fib_slots`` costs
``33 * 8 * fib_slots`` bytes — fine at node scale (33 KB at 128
slots), deliberately gated by ``fib_lpm_mem_mb`` at internet scale,
where the operator sets ``fib_lpm_plen_caps`` to the feed's real
length distribution (docs/ROUTING.md has the formula and a worked
1M-route example).
"""

from __future__ import annotations

import functools
import os
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

# IPv4 prefix lengths /0 .. /32 — one plane each.
LPM_LENGTHS = 33

# pre-masked network masks per length (Python ints, trace-time consts)
_ADDR_MAX = (1 << 32) - 1
LPM_MASKS: Tuple[int, ...] = tuple(
    (_ADDR_MAX ^ ((1 << (32 - L)) - 1)) if L else 0
    for L in range(LPM_LENGTHS)
)

# plane pad value: sorts at/after every real prefix (a REAL 0xFFFFFFFF
# /32 entry still resolves — searchsorted-left lands on the live copy
# first, and the count guard rejects pure-pad hits)
LPM_PAD = _ADDR_MAX

# Stride-table accelerator (the ROADMAP item-5 "per-/8 stride tables",
# generalized per length): each populated length gets a direct hint
# table indexed by the query's top ``b = min(L, LPM_HINT_BITS,
# bit_length(cap))`` bits, bounding the binary search to ONE bucket.
# The bucket size is STRUCTURAL — at most 2^(L-b) distinct prefixes of
# length L share b top bits (the staging dedupe guarantees distinct) —
# so the per-length step count is config-static and never depends on
# staged routes. A module constant, not a knob: the layout must be
# recoverable from the table SHAPES alone (the kernel sees only the
# tables pytree), and the memory cost is bounded by the caps it is
# derived from (~4 bytes per hint row; ~2.3 MB at a 1M-route
# shape, nothing at the default 128-slot FIB).
LPM_HINT_BITS = 16

# Planes below this capacity skip the hint layer entirely and search
# with one fused ``searchsorted``: at small N the flat binary search
# is already a handful of cache-resident probes, while the unrolled
# bounded bisection costs ~50 HLO ops per length at COMPILE time —
# a default config populates all 33 lengths, and fattening every step
# variant's program for planes the hint cannot speed up measurably
# slowed the whole test tier (compile-time, not run-time).
LPM_HINT_MIN = 8192


def lpm_hint_min() -> int:
    """The hint-engage threshold: planes at/above this capacity get a
    stride hint table. ``VPPT_LPM_HINT_MIN`` overrides the default
    (tests use it to engage the hint layer at small shapes). An env
    var, not a config field, because the layout must be recoverable
    from table SHAPES alone and must agree between builder staging
    and the device kernel within one process — the VPPT_SESS_ELECTION
    pattern."""
    try:
        return int(os.environ.get("VPPT_LPM_HINT_MIN", LPM_HINT_MIN))
    except ValueError:
        return LPM_HINT_MIN


def lpm_hint_layout(
    caps, hint_min: int | None = None,
) -> Tuple[Tuple[Tuple[int, int, int], ...], int]:
    """((b_bits, hint_offset, search_steps) per length, total hint
    rows). Offset -1 = no hint (length unpopulated, or /0 — a single
    possible prefix needs no search at all). Pure function of the
    capacity vector (and the process-wide engage threshold — see
    ``lpm_hint_min``), so builder staging and the device kernel
    derive the SAME layout from config and shapes respectively."""
    if hint_min is None:
        hint_min = lpm_hint_min()
    rows = []
    off = 0
    for length in range(LPM_LENGTHS):
        cap = caps[length]
        # jax-ok: caps are Python ints (config knob values or array
        # SHAPES) — the layout is trace-time static by construction
        if cap < hint_min or length == 0:
            rows.append((0, -1, 0))
            continue
        b = min(length, LPM_HINT_BITS, max(1, (cap - 1).bit_length()))
        bucket = min(cap, 1 << (length - b))
        # a lower-bound bisection over n entries has n + 1 outcomes:
        # bit_length(n) steps, one more than bit_length(n - 1) when a
        # bucket is full
        rows.append((b, off, bucket.bit_length()))
        off += (1 << b) + 1
    return tuple(rows), off


def lpm_field(length: int) -> str:
    """DataplaneTables field name of one length's prefix plane."""
    return f"fib_lpm_p{length}"


LPM_FIELDS: Tuple[str, ...] = tuple(lpm_field(L) for L in range(LPM_LENGTHS))


def lpm_len_caps(config) -> Tuple[int, ...]:
    """Per-length plane capacities [33] of one config. Disabled
    configs (knob dense, or the worst-case structure busts
    ``fib_lpm_mem_mb``) carry all-zero caps — every plane is a
    zero-width placeholder and the LPM kernels compile to an
    unconditional miss (never selected; the BV placeholder pattern)."""
    if not lpm_enabled_for(config):
        return (0,) * LPM_LENGTHS
    return _raw_len_caps(config)


def _raw_len_caps(config) -> Tuple[int, ...]:
    """The knob's capacity vector before the enable gate: explicit
    ``fib_lpm_plen_caps`` entries (index = prefix length, missing
    tail = 0), or every length sized to ``fib_slots``."""
    caps = tuple(getattr(config, "fib_lpm_plen_caps", ()) or ())
    if caps:
        caps = tuple(int(c) for c in caps)[:LPM_LENGTHS]
        return caps + (0,) * (LPM_LENGTHS - len(caps))
    return (int(config.fib_slots),) * LPM_LENGTHS


def lpm_plane_bytes(config) -> int:
    """Device bytes of the full LPM structure under this config's
    capacity vector (the ``fib_lpm_mem_mb`` gate's input and the
    ``vpp_tpu_fib_plane_bytes`` gauge): 2 uint32 rows per slot per
    plane + the stride hint tables + the count vector."""
    caps = _raw_len_caps(config)
    _rows, hint = lpm_hint_layout(caps)
    return sum(2 * 4 * c for c in caps) + 4 * hint + 4 * LPM_LENGTHS


def lpm_enabled_for(config) -> bool:
    """Whether this config allocates (and commit-time builds) the LPM
    planes: explicit ``fib_impl: lpm`` always (``pallas`` rides the
    SAME planes — ISSUE 16); ``auto`` only when the worst-case
    structure fits ``fib_lpm_mem_mb`` (the ``bv_enabled_for``
    discipline)."""
    knob = getattr(config, "fib_impl", "auto")
    if knob in ("lpm", "pallas"):
        return True
    if knob != "auto":
        return False
    cap_mb = int(getattr(config, "fib_lpm_mem_mb", 256))
    return lpm_plane_bytes(config) <= cap_mb * (1 << 20)


def populated_lengths(config) -> Tuple[int, ...]:
    """The config-static populated-length tuple, longest first — the
    lengths the compiled LPM kernel searches. Derived from capacities
    (cap 0 = plane absent), NEVER from staged routes: churn moves
    device values only, so the step program never retraces."""
    caps = lpm_len_caps(config)
    return tuple(L for L in range(LPM_LENGTHS - 1, -1, -1) if caps[L] > 0)


def ecmp_capacity(config) -> Tuple[int, int]:
    """(groups G, ways W) of the ECMP member tables. Groups 0 (the
    default) carries [1, 1] placeholders — no route can reference a
    group (TableBuilder refuses set_nh_group), the resolver's group
    branch stays compiled but dead."""
    g = int(getattr(config, "fib_ecmp_groups", 0))
    if g <= 0:
        return 1, 1
    return g, int(getattr(config, "fib_ecmp_ways", 8))


# --- device kernel -----------------------------------------------------


def fib_lookup_lpm(tables, pkts):
    """The LPM ip4-lookup (the ``fib_fn`` composed for
    ``fib_impl: lpm`` — pipeline/graph.py), returning the same
    ``FibResult`` as the dense path through the same shared resolver.

    The Python loop below is TRACE-TIME: it unrolls over the
    config-static populated lengths (zero-width planes skipped by
    shape — no tracer branching), longest first so the first hit IS
    the longest match. Ties inside a length are impossible (one masked
    prefix per length after staging dedupe), and duplicate staged
    prefixes keep the lowest slot — the dense argmax semantics.

    Each per-length search goes through the stride hint table
    (``fib_lpm_hint``; layout recovered from the plane SHAPES): two
    hint gathers bound the bisection to one top-bits bucket, so the
    unrolled step count per length is the STRUCTURAL bucket bound
    (config-static), not log2 of the whole plane — at a BGP-shaped 1M
    table that is ~4x fewer probe gathers than a flat searchsorted
    per length. A hint field whose shape disagrees with the derived
    layout (hand-built tables) falls back to the flat search."""
    from vpp_tpu.ops.fib import fib_flow_mix, resolve_fib_slot

    dst = pkts.dst_ip
    slot = jnp.zeros(dst.shape, jnp.int32)
    found = jnp.zeros(dst.shape, bool)
    cnt = tables.fib_lpm_cnt
    caps = tuple(getattr(tables, lpm_field(L)).shape[1]
                 for L in range(LPM_LENGTHS))
    layout, hint_rows = lpm_hint_layout(caps)
    hint = tables.fib_lpm_hint
    # jax-ok: shape compare — trace-time static, not a tracer branch
    use_hint = hint.shape[0] == hint_rows and hint_rows > 0
    for L in range(LPM_LENGTHS - 1, -1, -1):
        plane = getattr(tables, lpm_field(L))
        # jax-ok: plane width is a trace-time-static SHAPE (the
        # config-static populated-length tuple), not a tracer branch
        if plane.shape[1] == 0:
            continue
        pfx = plane[0]
        top = plane.shape[1] - 1
        if L == 0:
            # one possible prefix (0/0): a populated plane matches all
            hit = jnp.broadcast_to(cnt[0] > 0, dst.shape)
            take = hit & ~found
            slot = jnp.where(take, plane[1][0].astype(jnp.int32), slot)
            found = found | hit
            continue
        m = dst & jnp.uint32(LPM_MASKS[L])
        b, off, steps = layout[L]
        # jax-ok: layout is derived from shapes — trace-time static
        if use_hint and off >= 0:
            t = (m >> (32 - b)).astype(jnp.int32)
            lo = hint[off + t]
            hi = hint[off + t + 1]
            for _ in range(steps):
                mid = (lo + hi) >> 1
                p = pfx[jnp.clip(mid, 0, top)]
                less = p < m
                active = lo < hi
                lo = jnp.where(active & less, mid + 1, lo)
                hi = jnp.where(active & ~less, mid, hi)
            i = lo
        else:
            i = jnp.searchsorted(pfx, m, side="left").astype(jnp.int32)
        ic = jnp.clip(i, 0, top)
        hit = (pfx[ic] == m) & (i < cnt[L])
        take = hit & ~found
        slot = jnp.where(take, plane[1][ic].astype(jnp.int32), slot)
        found = found | hit
    return resolve_fib_slot(tables, slot, found, fib_flow_mix(pkts))


# --- pallas rung (ISSUE 16) -------------------------------------------
#
# The fib_impl ladder's "pallas" rung: the per-length searches above
# unroll into separate searchsorted/gather chains — each one streams
# the query vector and its plane through HBM independently. The fused
# kernel holds the populated planes VMEM-resident as [L, R, 128] tiles
# and, for a packet tile, scans every length's LIVE entries with a
# compare-and-min over (8, 128) prefix tiles: the masked query column
# is broadcast across lanes, so no step gathers (Mosaic lowers no
# per-packet gather out of a vector). The scan is linear in the live
# routes, which is why the rung is gated to planes that fit
# ``LPM_PALLAS_VMEM_BUDGET``; larger FIBs keep the bisection rung.
# Same dispatch discipline as the other kernels (ops/_pallas.py):
# compiled on a real TPU backend, the walk above everywhere else,
# interpret mode for the differential suite.

# packet rows per grid step, scanned in sub-tiles of 128 rows
_LPM_PT = 1024
_LPM_SUB = 128
# plane entries per scan step: one (8, 128) int32 tile
_LPM_CHUNK = 8 * 128
# VMEM budget of the stacked prefix + slot planes (2 x 4 bytes per
# padded entry per populated length)
LPM_PALLAS_VMEM_BUDGET = 4 << 20
_SLOT_MISS = 0x7FFFFFFF


def lpm_pallas_fits(config) -> bool:
    """Whether the populated planes, stacked at the widest one's padded
    width, fit the pallas rung's VMEM budget."""
    caps = [c for c in lpm_len_caps(config) if c > 0]
    if not caps:
        return False
    npad = -(-max(caps) // _LPM_CHUNK) * _LPM_CHUNK
    return 2 * 4 * len(caps) * npad <= LPM_PALLAS_VMEM_BUDGET


def _lpm_bias(x: jnp.ndarray) -> jnp.ndarray:
    """uint32 -> order-preserving int32 (flip the sign bit): Pallas
    TPU compares are happiest in int32, and LPM_PAD (0xFFFFFFFF)
    biases to int32 max — still sorting at/after every real prefix."""
    return lax.bitcast_convert_type(
        x ^ jnp.uint32(0x80000000), jnp.int32)


def _lpm_scan_kernel(mask_ref, cnt_ref, dst_ref, pfx_ref, slot_ref,
                     out_ref, *, nl: int):
    """One packet tile: for each 128-row sub-tile and each length
    (longest first), min-reduce the slots of the live entries whose
    prefix equals the masked query; the first length with a hit wins.
    Entries past a length's live count carry ``_SLOT_MISS`` (set by
    the wrapper), so a scan rounded up to whole tiles stays exact."""
    from vpp_tpu.ops._pallas import get_pallas

    pl, _pltpu = get_pallas("lpm_fused_lookup")
    sub = _LPM_SUB
    sign = jnp.int32(-(1 << 31))

    def rows(r, carry):
        start = pl.multiple_of(r * sub, sub)
        d = dst_ref[pl.ds(start, sub), :]          # [sub, 1]
        best = jnp.full((sub, 1), _SLOT_MISS, jnp.int32)
        for l in range(nl):
            m = jnp.broadcast_to((d & mask_ref[l]) ^ sign, (sub, 128))

            def scan(c, acc, l=l, m=m):
                c8 = pl.multiple_of(c * 8, 8)
                pf = pfx_ref[l, pl.ds(c8, 8), :]
                sl = slot_ref[l, pl.ds(c8, 8), :]
                for k in range(8):
                    acc = jnp.minimum(acc, jnp.where(
                        m == pf[k:k + 1, :], sl[k:k + 1, :], _SLOT_MISS))
                return acc

            n = (cnt_ref[l] + _LPM_CHUNK - 1) // _LPM_CHUNK
            acc = lax.fori_loop(
                0, n, scan, jnp.full((sub, 128), _SLOT_MISS, jnp.int32))
            hit = jnp.min(acc, axis=1, keepdims=True)
            best = jnp.where(best != _SLOT_MISS, best, hit)
        out_ref[pl.ds(start, sub), :] = best
        return carry

    lax.fori_loop(0, out_ref.shape[0] // sub, rows, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def lpm_fused_lookup(dst: jnp.ndarray, masks: jnp.ndarray,
                     cnt: jnp.ndarray, pfx_stack: jnp.ndarray,
                     slot_stack: jnp.ndarray, interpret: bool = False):
    """Fused all-lengths LPM search.

    dst [P] uint32 destinations; masks [L] uint32 the prefix masks of
    the stacked lengths, LONGEST FIRST — the first hit along them is
    the longest match; cnt [L] int32 live counts; pfx_stack [L, Npad]
    int32 biased (``_lpm_bias``) sorted prefixes; slot_stack [L, Npad]
    int32 owning slots. Returns (found [P] bool, slot [P] int32, 0 when
    miss) — bit-exact with ``lpm_fused_reference`` and the walk in
    ``fib_lookup_lpm`` over the same planes (tests/test_pallas_kernels.py
    holds them together)."""
    from vpp_tpu.ops._pallas import get_pallas

    pl, pltpu = get_pallas("lpm_fused_lookup")
    p = dst.shape[0]
    nl, npad = pfx_stack.shape
    pt = min(_LPM_PT, -(-p // _LPM_SUB) * _LPM_SUB)
    p_pad = -(-p // pt) * pt
    width = -(-max(npad, 1) // _LPM_CHUNK) * _LPM_CHUNK
    d = lax.bitcast_convert_type(dst.astype(jnp.uint32), jnp.int32)
    d = jnp.pad(d, (0, p_pad - p))[:, None]
    live = (lax.broadcasted_iota(jnp.int32, (nl, npad), 1)
            < cnt.astype(jnp.int32)[:, None])
    slots = jnp.where(live, slot_stack.astype(jnp.int32), _SLOT_MISS)
    pfx = jnp.pad(pfx_stack, ((0, 0), (0, width - npad)))
    slots = jnp.pad(slots, ((0, 0), (0, width - npad)),
                    constant_values=_SLOT_MISS)
    plane = pl.BlockSpec((nl, width // 128, 128), lambda i, *_: (0, 0, 0),
                         memory_space=pltpu.VMEM)
    plane_bytes = 2 * nl * width * 4
    out = pl.pallas_call(
        functools.partial(_lpm_scan_kernel, nl=nl),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(p_pad // pt,),
            in_specs=[
                pl.BlockSpec((pt, 1), lambda i, *_: (i, 0),
                             memory_space=pltpu.VMEM),
                plane,
                plane,
            ],
            out_specs=pl.BlockSpec((pt, 1), lambda i, *_: (i, 0),
                                   memory_space=pltpu.VMEM),
        ),
        out_shape=jax.ShapeDtypeStruct((p_pad, 1), jnp.int32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=2 * plane_bytes + (8 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=3 * p_pad * nl * width,
            bytes_accessed=plane_bytes + 2 * p_pad * 4,
            transcendentals=0,
        ),
    )(lax.bitcast_convert_type(masks.astype(jnp.uint32), jnp.int32),
      cnt.astype(jnp.int32), d, pfx.reshape(nl, width // 128, 128),
      slots.reshape(nl, width // 128, 128))
    slot = out[:p, 0]
    found = slot != _SLOT_MISS
    return found, jnp.where(found, slot, 0)


def lpm_fused_reference(dst, masks, cnt, pfx_stack, slot_stack):
    """The jnp twin of ``lpm_fused_lookup`` on its own signature: a
    left bisection per stacked length, first hit wins."""
    found = jnp.zeros(dst.shape, bool)
    slot = jnp.zeros(dst.shape, jnp.int32)
    top = pfx_stack.shape[1] - 1
    for l in range(pfx_stack.shape[0]):
        m = _lpm_bias(dst.astype(jnp.uint32) & masks[l])
        i = jnp.searchsorted(pfx_stack[l], m, side="left")
        ic = jnp.clip(i, 0, top).astype(jnp.int32)
        hit = (pfx_stack[l][ic] == m) & (i < cnt[l])
        slot = jnp.where(hit & ~found, slot_stack[l][ic], slot)
        found = found | hit
    return found, slot


def _lpm_stack(tables):
    """The populated planes stacked longest first, as the fused kernel
    takes them: (masks [L] uint32, cnt [L] int32, pfx [L, Npad] biased
    int32, slot [L, Npad] int32), or None when no length is populated.
    TRACE-TIME bookkeeping over already device-resident rows: the
    populated-length tuple stays config-static and zero-width planes
    never enter the stack."""
    caps = tuple(getattr(tables, lpm_field(L)).shape[1]
                 for L in range(LPM_LENGTHS))
    # jax-ok: shapes — the config-static populated-length tuple
    lens = tuple(L for L in range(LPM_LENGTHS - 1, -1, -1)
                 if caps[L] > 0)
    if not lens:
        return None
    npad = max(caps[L] for L in lens)
    pad_val = jnp.int32(0x7FFFFFFF)  # _lpm_bias(LPM_PAD)
    pfx_rows, slot_rows = [], []
    for L in lens:
        plane = getattr(tables, lpm_field(L))
        w = plane.shape[1]
        pfx_rows.append(jnp.pad(_lpm_bias(plane[0]), (0, npad - w),
                                constant_values=pad_val))
        slot_rows.append(jnp.pad(plane[1].astype(jnp.int32),
                                 (0, npad - w)))
    masks = jnp.asarray([LPM_MASKS[L] for L in lens], jnp.uint32)
    cnt = tables.fib_lpm_cnt[jnp.asarray(lens, jnp.int32)]
    return masks, cnt.astype(jnp.int32), jnp.stack(pfx_rows), \
        jnp.stack(slot_rows)


def _fib_lookup_lpm_pallas(tables, pkts, interpret: bool = False):
    """``fib_lookup_lpm`` with the per-length searches running in the
    fused kernel; the shared ``resolve_fib_slot`` tail keeps dense,
    LPM and pallas rungs bit-exact through the same route data."""
    from vpp_tpu.ops.fib import fib_flow_mix, resolve_fib_slot

    dst = pkts.dst_ip
    stack = _lpm_stack(tables)
    if stack is None:
        slot = jnp.zeros(dst.shape, jnp.int32)
        found = jnp.zeros(dst.shape, bool)
    else:
        found, slot = lpm_fused_lookup(dst, *stack, interpret=interpret)
    return resolve_fib_slot(tables, slot, found, fib_flow_mix(pkts))


def fib_lookup_lpm_fused(tables, pkts):
    """The fib_impl ladder's "pallas" rung (the ``fib_fn`` composed
    for ``fib_impl: pallas`` — pipeline/graph.py): fused kernel on a
    TPU backend, the unrolled LPM walk everywhere else. Bit-exact
    either way — same planes, same first-hit rule, same resolver."""
    from vpp_tpu.ops._pallas import use_pallas

    if not use_pallas():
        return fib_lookup_lpm(tables, pkts)
    return _fib_lookup_lpm_pallas(tables, pkts)
