"""Reflective-flow session table: a W-way set-associative hash map in HBM.

Reference analog: VPP acl-plugin's reflexive ("reflect") ACL session
table — when a policy permits flow A→B, the reverse flow B→A is admitted
statefully without needing its own permit rule. The scale target is
Gryphon's hyperscale-gateway connection state (PAPERS.md): 10M+
concurrent sessions resident on the device.

Layout: every session column is a ``[n_buckets, W]`` array — the way
count W is carried IN THE SHAPE, so the jitted kernels never need a
config plumb and jax re-specializes per geometry automatically. A flow
hashes to ONE bucket; all W ways of the bucket are fetched with a single
row gather (``arr[bucket] -> [P, W]``), compared vectorized, and the
whole insert resolves in ONE election round:

  1. **exists pass** — one gather per column; live key matches anywhere
     in the bucket refresh the timestamp (idempotent insert), same key
     with different payload is a **conflict** (fail-closed, the caller
     drops and counts — misdelivering NAT replies is worse than
     dropping).
  2. **single election round via bucket representatives** — each
     bucket's first W pending packets (in packet-index order) are its
     *reps*; every pending packet compares its FULL key against its
     bucket's reps. The first rep with an equal key is the packet's
     **leader** (exactly the lowest-index packet of its flow: if any
     same-key packet made rep, the lower-index leader did too — never
     a hash-probabilistic dedup), and the leader's **rank** is the
     number of DISTINCT flows among the reps before its slot (a
     pairwise dedup over the W reps — NOT the raw slot index, which
     duplicate packets of a bursty sibling flow would inflate,
     skipping free ways and victim-evicting live sessions for no
     reason). A packet that IS its own leader wins and takes the
     bucket's rank-th best way: free ways first (invalid and
     idle-expired ways rank alike, by ascending way index — reclaiming
     an expired way over a never-used one is immaterial, both are
     free; insert-time eviction preserved and the expired case counted
     ``reason=expired``), then LIVE ways oldest-``time`` first
     (**victim eviction** — a full bucket admits new flows by evicting
     longest-idle sessions, counted by reason).
     Ranks are dense and unique per bucket, so distinct flows NEVER
     collide on a way; followers inherit their leader's outcome (same
     payload → satisfied, different → deterministic conflict, leader
     not a rep → failed). The only intra-batch failure mode is a
     flow's FIRST packet falling past the bucket's W-pending-packet
     rep window in one vector (``failed_mask``; the flow retries on
     its next packet). Winners are written with ONE scatter round. Two equivalent rep strategies (differentially
     tested identical, selected at trace time):

       * ``claim`` — W iterations of scatter-min over an [n_buckets]
         claim array (iteration j crowns rep j): O(W·n_buckets)
         memset per insert, cost SCALES with the table.
       * ``sort`` — ONE single-operand sort of a packed
         (pending, bucket, packet-index) key; equal buckets form runs
         in packet order and reps are the first W run members:
         O(B log B) in the BATCH, table-size independent — mandatory
         at the 10M+ regime and measured faster at every deployed
         size on CPU too. (A variadic argsort is ~10x the cost of a
         single-key sort on the CPU backend, hence the bit-packing;
         when batch-index + bucket bits don't fit 32 together the
         code pays the stable argsort instead — bucket bits are NEVER
         masked below 2^30 buckets, because a masked merge would not
         only waste rep slots: it inflates a winner's rank past its
         own bucket's rep count, and a rank-inflated winner skips
         free ways and victim-evicts a LIVE session it had no reason
         to touch.)

     ``auto`` therefore picks sort everywhere; claim remains selectable
     (VPPT_SESS_ELECTION=claim) as the comparison baseline and
     ``bench.py``'s ``sess_election_*`` shoot-out re-measures both.

Aging is amortized: ``session_sweep`` clears a fixed stride of buckets
per fused pipeline step (cursor threaded through the tables pytree), so
idle-expiry reclamation is O(stride) per step instead of a monolithic
full-table pass — nanoPU's bounded-per-step framing (PAPERS.md).
``session_expire`` remains as the on-demand bulk reclaim (CLI / tests /
idle-node maintenance).
"""

from __future__ import annotations

import functools
import os
from typing import Tuple

import jax
import jax.numpy as jnp

# Plain int, not jnp: a module-level device scalar would (a) initialize
# the JAX backend at import and (b) be captured as an embedded device
# constant in every jitted program using it.
_BIG = 0x7FFFFFFF


def election_mode(n_slots: int) -> str:
    """Trace-time election strategy (module doc). Env override first;
    ``auto`` is sort — table-size independent (claim's scatter-min
    scales with n_slots, untenable at the 10M regime), with the bench
    shoot-out re-validating the choice per backend each round."""
    mode = os.environ.get("VPPT_SESS_ELECTION", "auto")
    if mode in ("claim", "sort"):
        return mode
    return "sort"

from vpp_tpu.pipeline.tables import DataplaneTables
from vpp_tpu.pipeline.vector import PacketVector

# Legacy linear-probe depth — kept ONLY for the bench's old-vs-new
# baseline (``hashmap_insert_linear``); the set-associative table's
# probe window is the bucket's way count, carried in the array shape.
SESS_PROBES = 4


def _hash_mix(src: jnp.ndarray, dst: jnp.ndarray, ports: jnp.ndarray,
              proto: jnp.ndarray) -> jnp.ndarray:
    """Full 32-bit multiplicative xor mix of the 5-tuple (uint32).
    Callers mask it to a bucket — the whole table, or a tenant's
    slice (``tenant_bucket``)."""
    h = src * jnp.uint32(0x9E3779B1)
    h ^= dst * jnp.uint32(0x85EBCA77)
    h ^= ports * jnp.uint32(0xC2B2AE3D)
    h ^= proto.astype(jnp.uint32) * jnp.uint32(0x27D4EB2F)
    h ^= h >> 15
    h = h * jnp.uint32(0x2545F491)
    h ^= h >> 13
    return h


def _hash(src: jnp.ndarray, dst: jnp.ndarray, ports: jnp.ndarray, proto: jnp.ndarray,
          n_buckets: int) -> jnp.ndarray:
    """Multiplicative xor hash of the 5-tuple into [0, n_buckets)."""
    mix = _hash_mix(src, dst, ports, proto)
    return (mix & jnp.uint32(n_buckets - 1)).astype(jnp.int32)


def tenant_bucket(tables: DataplaneTables, key_a: jnp.ndarray,
                  key_b: jnp.ndarray, mix: jnp.ndarray,
                  base: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Tenant-sliced bucket of a hashed key (ISSUE 14): the key's
    tenant — ``key_tenant`` on the key's ADDRESS PAIR, symmetric under
    src/dst swap so forward insert and reply lookup agree — selects a
    contiguous bucket range ``[base[t], base[t] + mask[t] + 1)`` in
    GLOBAL bucket units, and the hash lands inside it. A full slice
    can only contend/evict WITHIN its owning tenant's range (never
    cross-tenant eviction — structural, not policed). With the default
    single-tenant staging (base 0, full-table mask) the result is
    bit-identical to the unsliced ``_hash``."""
    from vpp_tpu.tenancy.derive import key_tenant

    kt = key_tenant(tables, key_a, key_b)
    return (base[kt]
            + (mix & mask[kt].astype(jnp.uint32)).astype(jnp.int32))


def _pack_ports(sport: jnp.ndarray, dport: jnp.ndarray) -> jnp.ndarray:
    return (sport.astype(jnp.uint32) << 16) | dport.astype(jnp.uint32)


def canon_mix(src: jnp.ndarray, dst: jnp.ndarray, sport: jnp.ndarray,
              dport: jnp.ndarray, proto: jnp.ndarray) -> jnp.ndarray:
    """Direction-invariant (symmetric) 5-tuple mix: the tuple is
    canonicalized — endpoints ordered by address, ports following their
    endpoints (hairpin src==dst orders by port) — before the same
    ``_hash_mix``, so a flow's forward packet and its reply produce the
    SAME mix without knowing which direction they are.

    This is the ``sess_hash: "sym"`` bucket family (docs/FLEET.md): a
    stateless host tier in front of N dataplanes can compute a packet's
    session BUCKET without knowing flow direction, which is what makes
    bucket-range flow steering (and range-scoped session migration)
    possible. Only the BUCKET changes vs "fwd" — stored keys and key
    comparison stay the forward tuple, so hit/insert semantics are
    untouched. vpp_tpu/fleet/hashring.py carries the bit-identical
    NumPy twin for the steering tier; keep the two in sync."""
    swap = (src > dst) | ((src == dst) & (sport > dport))
    a = jnp.where(swap, dst, src)
    b = jnp.where(swap, src, dst)
    ports = jnp.where(swap, _pack_ports(dport, sport),
                      _pack_ports(sport, dport))
    return _hash_mix(a, b, ports, proto)


# --- bucket-axis sharding (ISSUE 12; vpp_tpu/parallel/partition.py) ---
#
# Under the mesh, each session column is the LOCAL bucket-range shard
# of the node's grid ([NB/S, W] inside shard_map). Bit-exactness vs the
# standalone table comes from hashing against the GLOBAL bucket count
# (local_buckets * shards) and masking to the shard's contiguous
# ownership range: every flow lands in the same global bucket it would
# standalone, exactly one shard owns it, and the per-packet outcomes
# (hit, insert, conflict, eviction) are recombined with one psum —
# sound because a non-owning shard contributes exactly zero. Elections
# stay shard-local and bit-exact: packets only ever contend within one
# bucket, and a bucket's full contender set lives on its owning shard.


def global_buckets(n_local: int, shard) -> int:
    """GLOBAL bucket count of a (possibly sharded) grid — the hash
    modulus that keeps sharded bucket assignment identical to the
    standalone table."""
    return n_local * (shard.shards if shard is not None else 1)


def shard_buckets(h_global: jnp.ndarray, n_local: int, shard):
    """(own [P] bool, local_bucket [P]) of globally-hashed buckets on
    this shard. Ownership is blocked: shard s owns global buckets
    [s·n_local, (s+1)·n_local), so the local row is the low bits
    (n_local is a power of two) and ownership is the high bits."""
    from jax import lax

    idx = lax.axis_index(shard.axis).astype(jnp.int32)
    own = (h_global // n_local) == idx
    return own, h_global & jnp.int32(n_local - 1)


def _shard_sum(x: jnp.ndarray, shard) -> jnp.ndarray:
    from jax import lax

    return lax.psum(x, shard.axis)


def shard_combine_mask(mask: jnp.ndarray, shard) -> jnp.ndarray:
    """Recombine per-shard ownership-masked bool masks: exactly one
    shard can assert a packet, so psum of the int form is 0/1."""
    if shard is None:
        return mask
    return _shard_sum(mask.astype(jnp.int32), shard) > 0


def shard_combine_value(val: jnp.ndarray, mask: jnp.ndarray, shard):
    """Recombine per-shard values defined only on the owning shard
    (``mask``): non-owners contribute 0, so psum reproduces the owning
    shard's value exactly."""
    if shard is None:
        return val
    return _shard_sum(jnp.where(mask, val, jnp.zeros_like(val)), shard)


def _shard_flat_slot(hit_idx: jnp.ndarray, mask: jnp.ndarray,
                     n_local: int, ways: int, shard):
    """Translate a GLOBAL flat slot index (bucket·W + way) into this
    shard's ownership mask + LOCAL flat index (for touch scatters)."""
    bucket_g = hit_idx // ways
    own, local_b = shard_buckets(bucket_g, n_local, shard)
    return mask & own, local_b * ways + hit_idx % ways


def session_lookup_reverse(
    tables: DataplaneTables, pkts: PacketVector, now=None,
    tnt: bool = False, impl: str = "gather", sym: bool = False
) -> jnp.ndarray:
    """Is each packet the *return* traffic of an established session?

    Looks up the reversed 5-tuple (dst→src, dport→sport) in the table.
    Returns a bool mask [P]. With ``now``, entries idle longer than
    ``tables.sess_max_age`` are dead even before any reclamation sweeps
    them — timeout precision is in-kernel (VPP's session timers fire
    per-worker; ours are evaluated per lookup). ``impl`` is the
    session_impl ladder rung (trace-time static, step-factory gate):
    ``pallas`` probes through the fused kernel (gather rung off-TPU —
    bit-exact either way)."""
    n_buckets = tables.sess_valid.shape[0]
    key_src = pkts.dst_ip
    key_dst = pkts.src_ip
    key_ports = _pack_ports(pkts.dport, pkts.sport)
    key_proto = pkts.proto
    # jax-ok: tnt/sym are trace-time-static step-factory gates (Python
    # bools baked into the jit key), not tracer branches. In sym mode
    # the mix is computed on the packet AS SEEN (canonicalization makes
    # it direction-invariant — identical to the forward key's canon
    # mix); key comparison below stays the reconstructed forward tuple.
    if sym:
        mix = canon_mix(pkts.src_ip, pkts.dst_ip, pkts.sport,
                        pkts.dport, pkts.proto)
    else:
        mix = _hash_mix(key_src, key_dst, key_ports, key_proto)
    if tnt:
        b = tenant_bucket(tables, key_src, key_dst, mix,
                          tables.tnt_sess_base, tables.tnt_sess_mask)
    else:
        b = (mix & jnp.uint32(n_buckets - 1)).astype(jnp.int32)
    # jax-ok: impl is a trace-time-static ladder rung, not a tracer
    # branch. No-age lookups pass (0, _BIG) — vacuously true on a
    # non-negative tick clock (see _sess_probe_dispatch).
    if impl == "pallas":
        found, _first = _sess_probe_dispatch(
            tables, b, key_src, key_dst, key_ports, key_proto,
            now if now is not None else 0,
            tables.sess_max_age if now is not None else _BIG)
        return found
    # ONE row gather per column fetches the whole bucket ([P, W]): the
    # ways are contiguous, so this is the cheapest gather shape the
    # table can offer — no probe chain, no cross-way dependency.
    slot_match = (
        (tables.sess_valid[b] == 1)
        & (tables.sess_src[b] == key_src[:, None])
        & (tables.sess_dst[b] == key_dst[:, None])
        & (tables.sess_ports[b] == key_ports[:, None])
        & (tables.sess_proto[b] == key_proto[:, None])
    )
    if now is not None:
        slot_match = slot_match & (
            now - tables.sess_time[b] <= tables.sess_max_age
        )
    return jnp.any(slot_match, axis=1)


def session_lookup_reverse_idx(
    tables: DataplaneTables, pkts: PacketVector, now, shard=None,
    tnt: bool = False, impl: str = "gather", sym: bool = False
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Like session_lookup_reverse, but also returns the matched FLAT
    slot index [P] (bucket·W + way; undefined where not found) so the
    pipeline can refresh ``sess_time`` — active flows must not expire
    mid-flow.

    With ``shard`` (the bucket-sharded mesh table), the hash targets
    the GLOBAL bucket space, each shard probes only buckets it owns and
    one psum recombines: ``found``/``hit_idx`` come back replicated and
    identical to the standalone lookup, with ``hit_idx`` staying the
    GLOBAL flat index (the touch path re-derives local ownership)."""
    n_buckets, ways = tables.sess_valid.shape
    key_src = pkts.dst_ip
    key_dst = pkts.src_ip
    key_ports = _pack_ports(pkts.dport, pkts.sport)
    key_proto = pkts.proto
    # jax-ok: tnt/sym are trace-time-static step-factory gates (Python
    # bools baked into the jit key), not tracer branches. The tenant
    # slice addresses GLOBAL bucket units, so the shard ownership
    # split below composes unchanged (docs/TENANCY.md). sym swaps ONLY
    # the bucket mix for the direction-invariant canon form (canon_mix
    # doc) — stored-key comparison stays the forward tuple.
    if sym:
        mix = canon_mix(pkts.src_ip, pkts.dst_ip, pkts.sport,
                        pkts.dport, pkts.proto)
    else:
        mix = _hash_mix(key_src, key_dst, key_ports, key_proto)
    if tnt:  # jax-ok: trace-time-static gate (the block comment above)
        b = tenant_bucket(tables, key_src, key_dst, mix,
                          tables.tnt_sess_base, tables.tnt_sess_mask)
    else:
        b = (mix & jnp.uint32(
            global_buckets(n_buckets, shard) - 1)).astype(jnp.int32)
    if shard is not None:
        own, bl = shard_buckets(b, n_buckets, shard)
    else:
        own, bl = None, b
    # jax-ok: impl is a trace-time-static ladder rung. The fused probe
    # serves the STANDALONE table only — sharded lookups keep the
    # gather rung (the psum recombination lives outside the kernel and
    # the ladder never selects pallas on a mesh; partition.py rejects
    # the knob at config time).
    if impl == "pallas" and shard is None:
        found, first = _sess_probe_dispatch(
            tables, b, key_src, key_dst, key_ports, key_proto,
            now, tables.sess_max_age)
        return found, b * ways + first
    slot_match = (
        (tables.sess_valid[bl] == 1)
        & (tables.sess_src[bl] == key_src[:, None])
        & (tables.sess_dst[bl] == key_dst[:, None])
        & (tables.sess_ports[bl] == key_ports[:, None])
        & (tables.sess_proto[bl] == key_proto[:, None])
        & (now - tables.sess_time[bl] <= tables.sess_max_age)
    )
    if own is not None:
        slot_match = slot_match & own[:, None]
    found = jnp.any(slot_match, axis=1)
    first = jnp.argmax(slot_match, axis=1)
    hit_idx = b * ways + first  # GLOBAL flat index in both modes
    if shard is not None:
        hit_idx = shard_combine_value(hit_idx, found, shard)
        found = shard_combine_mask(found, shard)
    return found, hit_idx


def session_batch_summary(
    tables: DataplaneTables, pkts: PacketVector, alive: jnp.ndarray, now,
    shard=None, tnt: bool = False, impl: str = "gather",
    sym: bool = False
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Batched hit summary for the two-tier fast/slow dispatch
    (pipeline/graph.py pipeline_step_auto): one reverse lookup yields
    ``(hits, hit_idx, all_hit)`` where ``hits`` masks alive packets
    admitted by a live reflective session, ``hit_idx`` their matched
    slots (for session_touch) and ``all_hit`` the batch-level scalar
    predicate — EVERY alive packet rides an established session, so the
    classify-free fast kernel is bit-exact for the whole vector. A
    batch with no alive packets is vacuously all-hit (the fast kernel
    is a no-op on it, exactly like the full chain).

    Sharded, the psum inside the lookup already makes ``hits``
    replicated across the rule axis, so ``all_hit`` is SPMD-uniform by
    construction; the caller (pipeline_step_auto) additionally pmins
    the flag so the lax.cond dispatch provably can't diverge."""
    found, hit_idx = session_lookup_reverse_idx(tables, pkts, now,
                                                shard=shard, tnt=tnt,
                                                impl=impl, sym=sym)
    hits = found & alive
    all_hit = jnp.all(hits == alive)
    return hits, hit_idx, all_hit


def session_hit_age(
    tables: DataplaneTables, hit_idx: jnp.ndarray, mask: jnp.ndarray, now,
    shard=None
) -> jnp.ndarray:
    """Ticks since the matched session's last hit, per packet (int32
    [P]; 0 where ``mask`` is False). Read BEFORE session_touch — the
    touch resets the timestamp to ``now``. One flat gather; feeds the
    ML stage's session-age feature (ops/mlscore.py). Sharded, the
    owning shard gathers and a psum replicates the timestamp — masked
    packets read 0 from every shard, exactly like standalone."""
    n_buckets, ways = tables.sess_valid.shape
    if shard is not None:
        own_mask, local = _shard_flat_slot(hit_idx, mask, n_buckets,
                                           ways, shard)
        safe = jnp.clip(local, 0, n_buckets * ways - 1)
        t = shard_combine_value(
            tables.sess_time.reshape(-1)[safe], own_mask, shard)
        return jnp.where(mask, now - t, 0).astype(jnp.int32)
    safe = jnp.clip(hit_idx, 0, n_buckets * ways - 1)
    t = tables.sess_time.reshape(-1)[safe]
    return jnp.where(mask, now - t, 0).astype(jnp.int32)


def session_touch(
    tables: DataplaneTables, hit_idx: jnp.ndarray, mask: jnp.ndarray, now,
    shard=None
) -> DataplaneTables:
    """Refresh sess_time for matched sessions (keepalive on traffic).
    ``hit_idx`` is flat (bucket·W + way, session_lookup_reverse_idx —
    GLOBAL in both modes; sharded, only the owning shard scatters)."""
    n_buckets, ways = tables.sess_valid.shape
    if shard is not None:
        mask, hit_idx = _shard_flat_slot(hit_idx, mask, n_buckets, ways,
                                         shard)
    widx = jnp.where(mask, hit_idx, n_buckets * ways)
    return tables._replace(
        sess_time=tables.sess_time.at[widx // ways, widx % ways].set(
            now, mode="drop")
    )


def _elect(cand: jnp.ndarray, slot: jnp.ndarray, n_slots: int) -> jnp.ndarray:
    """One election round: among candidate packets contending for the
    same flat slot id, the lowest packet index wins. Strategy ladder in
    the module doc (claim scatter-min vs stable sort) — semantics are
    identical by construction, picked at trace time. Used by the
    legacy linear-probe baseline; the set-associative insert uses the
    ranked form (``_elect_rank``)."""
    batch = slot.shape[0]
    p_idx = jnp.arange(batch, dtype=jnp.int32)
    # jax-ok: n_slots is a shape-derived Python int — election_mode is a
    # trace-time strategy pick, not a tracer branch
    if election_mode(n_slots) == "claim":
        claim = jnp.full((n_slots,), _BIG, dtype=jnp.int32)
        claim = claim.at[jnp.where(cand, slot, n_slots)].min(
            p_idx, mode="drop")
        return cand & (claim[slot] == p_idx)
    slot_key = jnp.where(cand, slot, n_slots)  # non-cands sort last
    order = jnp.argsort(slot_key)               # stable (jnp default)
    ss = slot_key[order]
    first_of_run = jnp.concatenate(
        [jnp.ones((1,), bool), ss[1:] != ss[:-1]])
    return jnp.zeros(batch, bool).at[order].set(
        first_of_run & (ss < n_slots))


def _bucket_reps(h: jnp.ndarray, pending: jnp.ndarray, n_buckets: int,
                 ways: int) -> jnp.ndarray:
    """Per packet, the packet indices of (up to) the first ``ways``
    pending packets of its bucket in ascending packet-index order — a
    [B, ways] matrix with sentinel B where the bucket has fewer pending
    members. The claim/sort strategy ladder (module doc): claim's j-th
    scatter-min iteration crowns exactly the (j+1)-lowest remaining
    packet index per bucket, which IS the j-th member of the bucket's
    run in the sorted order — bit-identical by construction. Sort mode
    packs (pending, bucket, packet index) into ONE 32-bit key when the
    bit widths fit, and otherwise falls back to a stable variadic
    argsort; bucket ids are NEVER masked to force the packed form —
    the module doc explains why a masked merge would inflate winner
    ranks into spurious victim evictions of live ways."""
    batch = pending.shape[0]
    p_idx = jnp.arange(batch, dtype=jnp.int32)
    # jax-ok: n_buckets/ways are shape-derived Python ints — trace-time
    # strategy pick, not a tracer branch
    if election_mode(n_buckets * ways) == "claim":
        reps = []
        remaining = pending
        for _ in range(ways):
            claim = jnp.full((n_buckets,), _BIG, dtype=jnp.int32)
            claim = claim.at[
                jnp.where(remaining, h, n_buckets)
            ].min(p_idx, mode="drop")
            rep_j = claim[h]      # this round's winner of MY bucket
            remaining = remaining & ~(rep_j == p_idx)
            reps.append(jnp.where(rep_j == _BIG, batch, rep_j))
        return jnp.stack(reps, axis=1)
    # sort mode: ONE single-operand 32-bit sort. Packed key layout
    # (most → least significant): not-pending bit | bucket bits |
    # packet index — so pending packets sort first, grouped by bucket,
    # in packet order, and the index decodes straight back out.
    idx_bits = max((batch - 1).bit_length(), 1)
    bkt_bits = max((n_buckets - 1).bit_length(), 1)
    # the packed form is only sound when the FULL bucket id fits next
    # to the packet index: masked bucket bits merge runs across
    # buckets, and a merged run inflates winner ranks → spurious
    # victim eviction of live ways (module doc). Otherwise pay the
    # stable argsort (exact up to 2^30 buckets).
    # jax-ok: idx_bits/bkt_bits are shape-derived Python ints — the
    # packed-vs-argsort pick is trace-time static, not a tracer branch
    if idx_bits + bkt_bits <= 31:
        sk = jnp.sort(
            ((~pending).astype(jnp.uint32) << 31)
            | (h.astype(jnp.uint32) << idx_bits)
            | p_idx.astype(jnp.uint32)
        )
        order64 = None
        runid = sk >> idx_bits
        order = (sk & jnp.uint32((1 << idx_bits) - 1)).astype(jnp.int32)
    else:
        # 30 bucket bits, no room for the index — pay a stable
        # variadic argsort (slower on CPU, fine on accelerators)
        key31 = (((~pending).astype(jnp.uint32)) << 30) | (
            h.astype(jnp.uint32) & jnp.uint32((1 << 30) - 1))
        order64 = jnp.argsort(key31)  # stable (jnp default)
        sk = key31[order64]
        runid = sk
        order = order64
    pos = jnp.arange(batch, dtype=jnp.int32)
    run_start = jnp.concatenate(
        [jnp.ones((1,), bool), runid[1:] != runid[:-1]])
    # forward-fill each position with its run's start (the where()
    # plants start positions, cummax propagates them — sound because
    # positions are strictly increasing)
    start_pos = jax.lax.cummax(jnp.where(run_start, pos, 0))
    # the whole rep window in ONE [B, W] gather: rows start_pos..+W-1
    rp = start_pos[:, None] + jnp.arange(ways, dtype=jnp.int32)[None, :]
    rp_c = jnp.minimum(rp, batch - 1)
    if order64 is None:
        sk_at = sk[rp_c]      # one gather: run check AND packet index
        ok = (rp < batch) & ((sk_at >> idx_bits) == runid[:, None])
        rep = (sk_at & jnp.uint32((1 << idx_bits) - 1)).astype(jnp.int32)
    else:
        ok = (rp < batch) & (runid[rp_c] == runid[:, None])
        rep = order64[rp_c]
    rep_s = jnp.where(ok, rep, batch)
    # scatter the sorted-space rep rows back to packet order (order is
    # a permutation: every position is written exactly once)
    return jnp.zeros((batch, ways), jnp.int32).at[order].set(rep_s)


def hashmap_insert(
    valid: jnp.ndarray,
    time: jnp.ndarray,
    keys: Tuple[jnp.ndarray, ...],
    key_vals: Tuple[jnp.ndarray, ...],
    extras: Tuple[jnp.ndarray, ...],
    extra_vals: Tuple[jnp.ndarray, ...],
    h: jnp.ndarray,
    want: jnp.ndarray,
    now: jnp.ndarray,
    max_age=None,
) -> tuple:
    """Generic W-way set-associative batch insert (see module doc).

    ``keys``/``extras`` are the table's ``[n_buckets, W]`` column
    arrays, ``key_vals``/``extra_vals`` the per-packet values to store;
    ``h`` the per-packet home BUCKET. Matching on ``keys`` makes the
    insert idempotent (refreshes ``time``); ``extras`` are payload
    columns written but not compared for matching — but if an existing
    entry has the same key with *different* payload, the insert is a
    **conflict** (e.g. two SNAT'd flows whose hash-derived ports
    collide on the same reply 5-tuple): the entry is left untouched (no
    time refresh — the original flow owns the slot) and the packet is
    flagged so the caller can fail closed.

    With ``max_age``, entries idle past it count as dead: they neither
    match nor block — the insert reclaims their ways in-bucket
    (insert-time eviction). A bucket whose every way is LIVE admits the
    new flow anyway by evicting the oldest-``time`` way (victim
    policy); both reclaim flavors are reported so the caller can count
    ``{reason=expired|victim}``.

    Returns ``(valid, time, keys, extras, inserted, conflict, failed,
    evict_expired, evict_victim)`` — all masks [P]. ``failed`` marks
    packets that lost the single intra-batch election to a DIFFERENT
    flow targeting the same way (they retry on their flow's next
    packet; sustained failures mean heavy same-bucket pressure and are
    surfaced as a counter, never a silent skip).
    """
    n_buckets, ways = valid.shape
    batch = want.shape[0]
    keys = tuple(keys)
    extras = tuple(extras)

    # --- pass 1: one bucket-row gather per column; refresh / conflict ---
    vw = valid[h]                       # [P, W]
    tw = time[h]
    live = vw == 1
    if max_age is not None:
        live = live & (now - tw <= max_age)
    key_match = live
    for arr, val in zip(keys, key_vals):
        key_match = key_match & (arr[h] == val[:, None])
    exists = jnp.any(key_match, axis=1)
    exist_way = jnp.argmax(key_match, axis=1)

    def at_way(arr, way):
        """Single-element gather of each packet's (bucket, way) cell."""
        return arr[h, way]

    pay_same = jnp.ones_like(exists)
    for arr, val in zip(extras, extra_vals):
        pay_same = pay_same & (at_way(arr, exist_way) == val)
    conflict = want & exists & ~pay_same
    refresh = want & exists & pay_same
    refresh_slot = jnp.where(
        refresh, h * ways + exist_way, n_buckets * ways)
    pending = want & ~exists
    inserted = refresh

    shape = valid.shape

    def put(arr, val, idx):
        return arr.reshape(-1).at[idx].set(val, mode="drop").reshape(shape)

    # the refresh scatter lands BEFORE the election so victim
    # priorities see this batch's refreshes: a way refreshed in pass 1
    # is active *now*, and electing it as the oldest-time victim off
    # its stale pre-batch timestamp would evict the very flow that
    # just touched it (while still reporting that flow inserted=True).
    # One re-gathered row per packet; the chain time→scatter→gather is
    # linear so XLA aliases the buffer in place.
    time = put(time, jnp.broadcast_to(now, (batch,)).astype(time.dtype),
               refresh_slot)
    tw = time[h]

    # --- pass 2: ONE rep-based election round (module doc) ---
    p_idx = jnp.arange(batch, dtype=jnp.int32)
    reps = _bucket_reps(h, pending, n_buckets, ways)       # [B, W]
    # leader = first rep with MY full key. Because reps are scanned in
    # packet order and a flow's lowest-index pending packet makes rep
    # whenever ANY of its packets does, the leader is (a) exactly the
    # flow's first packet and (b) always its own leader — i.e. every
    # follower's leader IS a winner, so no winner[leader] indirection
    # is needed. No same-key rep => the flow's first packet fell past
    # the bucket's W-packet budget this batch => failed (retry). Key
    # columns are stacked so the whole rep comparison is ONE [B, W, K]
    # gather — gathers are the dominant unfusable op on CPU.
    kmat = jnp.stack([v.astype(jnp.uint32) for v in key_vals], axis=1)
    rep_c = jnp.minimum(reps, batch - 1)
    rk = kmat[rep_c]                                       # [B, W, K]
    same = (reps < batch) & jnp.all(
        rk == kmat[:, None, :], axis=2)                    # [B, W]
    found = jnp.any(same, axis=1)
    lead_slot = jnp.argmax(same, axis=1).astype(jnp.int32)  # first match
    leader = jnp.take_along_axis(rep_c, lead_slot[:, None], axis=1)[:, 0]
    winner = pending & found & (leader == p_idx)
    follower = pending & found & (leader != p_idx)
    # rank = DISTINCT flows among the reps before my leader's slot, NOT
    # the raw rep slot index: duplicate packets of one flow occupy rep
    # slots (the window is W pending packets) but must not inflate a
    # later flow's rank — a slot-index rank skips free ways and
    # victim-evicts a LIVE session whenever a sibling flow bursts >1
    # packet into the same batch (TCP retransmits / first-window
    # bursts). Dedup among W reps is one [B, W, W, K] pairwise compare;
    # ranks stay dense and unique per bucket (first-appearance order).
    ok_rep = reps < batch
    rep_dup = jnp.any(
        jnp.all(rk[:, :, None, :] == rk[:, None, :, :], axis=3)
        & jnp.tril(jnp.ones((ways, ways), bool), k=-1)[None]
        & ok_rep[:, :, None] & ok_rep[:, None, :], axis=2)  # [B, W]
    rep_new = (ok_rep & ~rep_dup).astype(jnp.int32)
    distinct_before = jnp.cumsum(rep_new, axis=1) - rep_new  # exclusive
    rank = jnp.take_along_axis(
        distinct_before, lead_slot[:, None], axis=1)[:, 0]

    # Way priority per bucket: free ways first (ascending way index —
    # the order is immaterial, only distinctness is), then live ways
    # oldest-time first (victims). time is non-negative (clock ticks),
    # so the free-way sentinel sorts strictly below every live key.
    # W is tiny and static: a counting rank over the [P, W, W] pairwise
    # compare (position of each way in priority order, ties broken by
    # way index) resolves every rank in ~6 fused elementwise ops —
    # measured ~35% faster end-to-end than the previous W-round
    # argmin-and-mask loop (4W sequential reductions), bit-identical.
    way_pri = jnp.where(live, tw,
                        -jnp.int32(1 << 30)
                        + jnp.arange(ways, dtype=jnp.int32)[None, :])
    wid = jnp.arange(ways, dtype=jnp.int32)
    ahead = (way_pri[:, :, None] > way_pri[:, None, :]) | (
        (way_pri[:, :, None] == way_pri[:, None, :])
        & (wid[None, :, None] > wid[None, None, :]))
    pos = jnp.sum(ahead, axis=2).astype(jnp.int32)         # [P, W] perm
    way = jnp.argmax(pos == rank[:, None], axis=1).astype(jnp.int32)
    pri_way = jnp.take_along_axis(way_pri, way[:, None], axis=1)[:, 0]

    # eviction classification without extra table gathers: the way's
    # pre-insert priority is negative exactly for FREE ways (invalid or
    # expired — vw, already in registers, splits those) and the live
    # time otherwise (victim)
    was_live = pri_way >= 0
    was_valid = jnp.take_along_axis(vw, way[:, None], axis=1)[:, 0] == 1
    evict_expired = winner & was_valid & ~was_live
    evict_victim = winner & was_live

    # one flat scatter round (flat 1D scatters measured ~25% cheaper
    # than the 2D advanced-index form on CPU); refresh timestamps do
    # NOT ride this scatter — they already landed in the pre-election
    # refresh pass, and both passes write the same `now`, so repeating
    # the refresh half would double the index set for no effect.
    slot = jnp.where(winner, h * ways + way, n_buckets * ways)
    keys = tuple(put(arr, val, slot) for arr, val in zip(keys, key_vals))
    extras = tuple(
        put(arr, val, slot) for arr, val in zip(extras, extra_vals))
    valid = put(valid, jnp.ones((batch,), valid.dtype), slot)
    time = put(time, jnp.broadcast_to(now, (batch,)).astype(time.dtype),
               slot)

    # followers inherit their leader's outcome (no table recheck: the
    # leader's write IS their key's slot). Same payload as the leader
    # => satisfied; different => intra-batch reply-key collision
    # (conflict, fail closed).
    # jax-ok: extra_vals is a Python tuple — payload arity is trace-time
    # static (reflective table has none, NAT table has five)
    if extra_vals:
        emat = jnp.stack(
            [v.astype(jnp.uint32) for v in extra_vals], axis=1)
        f_pay = jnp.all(emat[leader] == emat, axis=1)
    else:
        f_pay = jnp.ones_like(follower)
    conflict = conflict | (follower & ~f_pay)
    inserted = inserted | winner | (follower & f_pay)
    failed = pending & ~found
    return (valid, time, keys, extras, inserted, conflict, failed,
            evict_expired, evict_victim)


def session_insert(
    tables: DataplaneTables,
    pkts: PacketVector,
    want: jnp.ndarray,
    now: jnp.ndarray,
    shard=None,
    tnt: bool = False,
    sym: bool = False,
) -> tuple:
    """Insert forward 5-tuples of ``want`` packets; returns
    (tables, inserted, failed, evict_expired, evict_victim).

    Existing identical sessions are refreshed (timestamp), not
    duplicated; expired ways are reclaimed in place and a full bucket
    evicts its oldest entry (both counted by reason). ``failed`` marks
    packets that lost the intra-batch way election to a different flow:
    the flow retries on its next packet, and the caller counts the
    event (StepStats.sess_insert_fail → Prometheus) instead of
    degrading silently.

    Sharded, each shard elects and scatters ONLY the packets whose
    global bucket it owns: a bucket's full contender set lives on its
    owning shard, so the election (reps, leaders, ranks, way
    priorities) sees exactly the standalone contender set and the
    per-packet outcomes are bit-identical; one psum recombines the
    ownership-masked result masks.
    """
    key_vals = (
        pkts.src_ip,
        pkts.dst_ip,
        _pack_ports(pkts.sport, pkts.dport),
        pkts.proto,
    )
    # jax-ok: tnt/sym are trace-time-static step-factory gates (Python
    # bools baked into the jit key), not tracer branches. At insert
    # the packet IS the forward tuple, so sym's canon mix equals the
    # reply lookup's canon mix by construction (canon_mix doc).
    if sym:
        mix = canon_mix(pkts.src_ip, pkts.dst_ip, pkts.sport,
                        pkts.dport, pkts.proto)
    else:
        mix = _hash_mix(*key_vals)
    if tnt:  # jax-ok: trace-time-static gate (the block comment above)
        h = tenant_bucket(tables, key_vals[0], key_vals[1], mix,
                          tables.tnt_sess_base, tables.tnt_sess_mask)
    else:
        h = (mix & jnp.uint32(
            global_buckets(tables.sess_valid.shape[0], shard) - 1)
             ).astype(jnp.int32)
    if shard is not None:
        own, h = shard_buckets(h, tables.sess_valid.shape[0], shard)
        want = want & own
    (valid, time, keys, _, inserted, _, failed,
     ev_exp, ev_vic) = hashmap_insert(
        tables.sess_valid,
        tables.sess_time,
        (tables.sess_src, tables.sess_dst, tables.sess_ports, tables.sess_proto),
        key_vals,
        (),
        (),
        h,
        want,
        now,
        max_age=tables.sess_max_age,
    )
    if shard is not None:
        inserted = shard_combine_mask(inserted, shard)
        failed = shard_combine_mask(failed, shard)
        ev_exp = shard_combine_mask(ev_exp, shard)
        ev_vic = shard_combine_mask(ev_vic, shard)
    new_tables = tables._replace(
        sess_src=keys[0],
        sess_dst=keys[1],
        sess_ports=keys[2],
        sess_proto=keys[3],
        sess_valid=valid,
        sess_time=time,
    )
    return new_tables, inserted, failed, ev_exp, ev_vic


# --- amortized aging -------------------------------------------------


def _sweep_one(valid: jnp.ndarray, time: jnp.ndarray, cursor: jnp.ndarray,
               now, max_age, stride: int):
    """Age ONE stride of buckets starting at ``cursor`` (a multiple of
    the effective stride by construction: cursors start at 0 and only
    advance by it, and power-of-two bucket counts divide evenly).
    Returns (valid, next_cursor)."""
    from jax import lax

    n_buckets, _ways = valid.shape
    # jax-ok: stride is the trace-time-static sess_sweep_stride knob (a
    # Python int baked into the step-factory key), not a device value
    s = min(int(stride), n_buckets)
    v = lax.dynamic_slice(valid, (cursor, jnp.int32(0)),
                          (s, valid.shape[1]))
    t = lax.dynamic_slice(time, (cursor, jnp.int32(0)),
                          (s, valid.shape[1]))
    stale = (v == 1) & (now - t > max_age)
    valid = lax.dynamic_update_slice(
        valid, jnp.where(stale, 0, v), (cursor, jnp.int32(0)))
    return valid, lax.rem(cursor + s, jnp.int32(n_buckets))


def sweep_covered(steps: int, stride: int, tables,
                  bucket_axis: int = 0, passes: int = 1) -> bool:
    """True when ``steps`` fused steps — each running ``passes``
    pipeline passes, each pass sweeping ``stride`` buckets per table —
    have cycled the WHOLE ring of both session tables. The ONE copy of
    the lazy-expire coverage math (Dataplane / ClusterDataplane /
    MultiHostCluster all pace their bulk-pass skip on it; the cluster
    planes sweep twice per step and stack node axes ahead of the
    bucket axis). Coverage is paced by the LARGER bucket count —
    natsess_slots may exceed sess_slots."""
    if not stride:
        return False
    n_buckets = max(tables.sess_valid.shape[bucket_axis],
                    tables.natsess_valid.shape[bucket_axis])
    return steps * passes * stride >= n_buckets


def session_sweep(tables: DataplaneTables, now, stride: int) -> DataplaneTables:
    """Amortized on-device aging: clear idle-expired entries in ONE
    stride of buckets per table (reflective + NAT) and advance the
    sweep cursors. Runs INSIDE the fused pipeline step (graph.py
    ``_finish_step``), so reclamation cost is O(stride·W) per step —
    never a monolithic full-table pass — and a full cycle completes
    every ``n_buckets / stride`` steps. Entries the sweep has not
    reached yet are already invisible to lookups (in-kernel timeout)
    and reclaimable by insert-time eviction; the sweep only returns
    their ways to the free pool so occupancy reflects reality.
    ``stride`` is trace-time static (0 disables)."""
    # jax-ok: stride is the trace-time-static sess_sweep_stride knob —
    # 0-disables is a compile-time specialization, not a tracer branch
    if not stride:
        return tables
    sess_valid, sess_cur = _sweep_one(
        tables.sess_valid, tables.sess_time, tables.sess_sweep_cursor,
        now, tables.sess_max_age, stride)
    nat_valid, nat_cur = _sweep_one(
        tables.natsess_valid, tables.natsess_time,
        tables.natsess_sweep_cursor, now, tables.sess_max_age, stride)
    return tables._replace(
        sess_valid=sess_valid, sess_sweep_cursor=sess_cur,
        natsess_valid=nat_valid, natsess_sweep_cursor=nat_cur,
    )


def _session_expire_impl(tables: DataplaneTables, now, max_age) -> DataplaneTables:
    stale = (tables.sess_valid == 1) & (now - tables.sess_time > max_age)
    nat_stale = (tables.natsess_valid == 1) & (
        now - tables.natsess_time > max_age)
    return tables._replace(
        sess_valid=jnp.where(stale, 0, tables.sess_valid),
        natsess_valid=jnp.where(nat_stale, 0, tables.natsess_valid),
    )


# On-demand BULK reclaim of both session tables. Steady-state aging is
# the in-step session_sweep; this remains for explicit host-driven
# reclamation (tests, `clear sessions`-grade ops, idle nodes where no
# steps run to carry the sweep). Jitted: at 10M+ slots the eager form
# dispatches a dozen whole-table ops — one fused program keeps the
# bulk pass a single device call (now/max_age are traced scalars, so
# differing values never retrace).
session_expire = jax.jit(_session_expire_impl)


# --- legacy linear-probe baseline (bench comparison ONLY) ------------


def hashmap_insert_linear(
    valid: jnp.ndarray,
    time: jnp.ndarray,
    keys: Tuple[jnp.ndarray, ...],
    key_vals: Tuple[jnp.ndarray, ...],
    h: jnp.ndarray,
    want: jnp.ndarray,
    now: jnp.ndarray,
    probes: int = SESS_PROBES,
    max_age=None,
) -> tuple:
    """The pre-rework open-addressing insert (linear probing, one
    election + full scatter round PER PROBE), kept verbatim-in-spirit
    as the ``sess_insert_ns_pkt`` old-vs-new bench baseline
    (bench.py session_scale_bench). FLAT [n_slots] arrays. Not used by
    the pipeline."""
    n_slots = valid.shape[0]
    keys = tuple(keys)

    def live_at(idx):
        l = valid[idx] == 1
        if max_age is not None:
            l = l & (now - time[idx] <= max_age)
        return l

    def key_at(idx):
        same = live_at(idx)
        for arr, val in zip(keys, key_vals):
            same = same & (arr[idx] == val)
        return same

    exists = jnp.zeros_like(want)
    exist_idx = jnp.zeros_like(h)
    for p in range(probes):
        idx = (h + p) & (n_slots - 1)
        same = key_at(idx)
        exist_idx = jnp.where(same & ~exists, idx, exist_idx)
        exists = exists | same
    refresh = want & exists
    time = time.at[jnp.where(refresh, exist_idx, n_slots)].set(
        now, mode="drop")
    pending = want & ~exists
    for p in range(probes):
        idx = (h + p) & (n_slots - 1)
        cand = pending & ~live_at(idx)
        winner = _elect(cand, idx, n_slots)
        widx = jnp.where(winner, idx, n_slots)
        keys = tuple(
            arr.at[widx].set(val, mode="drop")
            for arr, val in zip(keys, key_vals)
        )
        valid = valid.at[widx].set(1, mode="drop")
        time = time.at[widx].set(now, mode="drop")
        pending = pending & ~key_at(idx)
    return valid, time, keys, pending


# --- pallas rung (ISSUE 16) -------------------------------------------
#
# The session_impl ladder's "pallas" rung: the reverse lookup above
# spends its time in SIX independent bucket-row gathers (one per
# column) whose [P, W] results stream through HBM five more times for
# the compares and the election. The fused kernel holds the session
# columns VMEM-resident (gated by ``session_pallas_fits`` — the MXU
# VMEM-budget discipline) and does gather + key-compare + age check +
# first-match election in one pass per packet tile. Sharded lookups
# keep the gather rung: the psum recombination happens OUTSIDE the
# kernel and the per-shard table slice already fits the gather path
# fine. Dispatch discipline as everywhere (ops/_pallas.py): compiled
# on a real TPU backend, the gather rung elsewhere, interpret mode for
# the differential suite.
# Slots per VMEM tile of a resident column: one (8, 128) int32 vreg.
# A bucket's ways sit in one tile when the way count divides it.
_SESS_TILE = 8 * 128
# packets per grid step: one (8, 128) result vreg
_SESS_PT = _SESS_TILE

# VMEM budget for the resident columns: 6 columns x 4 bytes per slot.
# The structural eligibility gate the selection ladder consumes
# (partition.py select_session_impl via dataplane); at the largest
# power-of-two table under it (1 << 18 slots, 6 MiB) the v5e compile
# in tests/test_tpu_compile.py holds the kernel under its VMEM limit.
SESS_PALLAS_VMEM_BUDGET = 8 << 20


def session_pallas_fits(config) -> bool:
    """Whether the whole session table (6 uint32-wide columns of
    ``sess_slots`` cells) fits the pallas rung's VMEM budget, with
    every bucket inside one resident tile. A table past the budget
    keeps the gather rung — HBM-resident columns are exactly what the
    gather path is for."""
    slots = int(getattr(config, "sess_slots", 0))
    ways = int(getattr(config, "sess_ways", 4))
    return (slots > 0 and 6 * 4 * slots <= SESS_PALLAS_VMEM_BUDGET
            and _SESS_TILE % ways == 0)


def _sess_probe_kernel(meta_ref, scal_ref, valid_ref, src_ref, dst_ref,
                       ports_ref, proto_ref, time_ref, out_ref, *,
                       ways: int, pt: int):
    """One packet tile: for each packet, load the resident (8, 128)
    tile of every column that holds its bucket (a dynamic index on the
    leading axis, never an in-register gather), compare the full
    reversed key + liveness + age over the bucket's lanes, and elect
    the first matching way (min way index == the gather rung's
    argmax-of-first-True). The per-packet result lands in the tile's
    (8, 128) result vreg at the packet's position."""
    slot = (jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0) * 128
            + jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1))
    now = scal_ref[0]
    max_age = scal_ref[1]

    def probe(i, acc):
        s0 = meta_ref[i] * ways
        t = s0 // _SESS_TILE
        off = s0 % _SESS_TILE
        match = ((valid_ref[t] == 1)
                 & (src_ref[t] == meta_ref[pt + i])
                 & (dst_ref[t] == meta_ref[2 * pt + i])
                 & (ports_ref[t] == meta_ref[3 * pt + i])
                 & (proto_ref[t] == meta_ref[4 * pt + i])
                 & (now - time_ref[t] <= max_age)
                 & (slot >= off) & (slot < off + ways))
        way = jnp.where(match, slot - off, _BIG)
        enc = jnp.min(jnp.min(way, axis=1, keepdims=True), axis=0,
                      keepdims=True)
        return jnp.where(slot == i, enc, acc)

    out_ref[0] = jax.lax.fori_loop(
        0, pt, probe, jnp.full((8, 128), _BIG, jnp.int32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def sess_probe_ways(b: jnp.ndarray, key_src: jnp.ndarray,
                    key_dst: jnp.ndarray, key_ports: jnp.ndarray,
                    key_proto: jnp.ndarray, valid: jnp.ndarray,
                    src: jnp.ndarray, dst: jnp.ndarray,
                    ports: jnp.ndarray, proto: jnp.ndarray,
                    time: jnp.ndarray, now, max_age,
                    interpret: bool = False):
    """Fused bucket probe + election over the session columns.

    ``b`` [P] home buckets; ``key_*`` [P] the (already reversed)
    5-tuple; ``valid``/``src``/``dst``/``ports``/``proto``/``time``
    the [NB, W] table columns; ``now``/``max_age`` scalars. Returns
    (found [P] bool, first [P] int32 — the matched way, 0 when no
    match, exactly the gather rung's ``argmax`` convention). Bit-exact
    with ``_probe_ways_reference`` (tests/test_pallas_kernels.py).

    The columns are reshaped to [NT, 8, 128] tiles (padded with
    invalid slots) and stay VMEM-resident; the packet's bucket and key
    ride in SMEM, one tile of ``_SESS_PT`` packets per grid step."""
    from vpp_tpu.ops._pallas import get_pallas

    pl, pltpu = get_pallas("sess_probe_ways")
    p = b.shape[0]
    nb, w = valid.shape
    # a frame smaller than one tile loops over its own packets only
    pt = min(_SESS_PT, p)
    nt = -(-p // pt)
    p_pad = nt * pt
    n_tiles = -(-(nb * w) // _SESS_TILE)

    def as_i32(x):
        x = jnp.asarray(x)
        if x.dtype == jnp.uint32:
            return jax.lax.bitcast_convert_type(x, jnp.int32)
        return x.astype(jnp.int32)

    def tiles(col):
        flat = as_i32(col).reshape(-1)
        flat = jnp.pad(flat, (0, n_tiles * _SESS_TILE - flat.shape[0]))
        return flat.reshape(n_tiles, 8, 128)

    # per tile: [b | src | dst | ports | proto], each pt packets long
    meta = jnp.stack([as_i32(x) for x in
                      (b, key_src, key_dst, key_ports, key_proto)])
    meta = jnp.pad(meta, ((0, 0), (0, p_pad - p)))
    meta = meta.reshape(5, nt, pt).transpose(1, 0, 2).reshape(-1)
    scal = jnp.stack([jnp.asarray(now, jnp.int32).reshape(()),
                      jnp.asarray(max_age, jnp.int32).reshape(())])
    table_spec = pl.BlockSpec((n_tiles, 8, 128), lambda i: (0, 0, 0),
                              memory_space=pltpu.VMEM)
    table_bytes = 6 * n_tiles * _SESS_TILE * 4
    enc = pl.pallas_call(
        functools.partial(_sess_probe_kernel, ways=w, pt=pt),
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((5 * pt,), lambda i: (i,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ] + [table_spec] * 6,
        out_specs=pl.BlockSpec((1, 8, 128), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((nt, 8, 128), jnp.int32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=2 * table_bytes + (8 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=16 * p_pad * _SESS_TILE,
            bytes_accessed=table_bytes + p_pad * (20 + 4),
            transcendentals=0,
        ),
    )(meta, scal, *(tiles(c) for c in (valid, src, dst, ports, proto,
                                        time)))
    enc = enc.reshape(nt, _SESS_TILE)[:, :pt].reshape(-1)[:p]
    found = enc != _BIG
    return found, jnp.where(found, enc, 0)


def _probe_ways_reference(b, key_src, key_dst, key_ports, key_proto,
                          valid, src, dst, ports, proto, time, now,
                          max_age):
    """The jnp twin of ``sess_probe_ways`` — the gather rung's exact
    math on the kernel's signature, so the differential suite can hold
    kernel and reference together without staging a full pipeline."""
    match = (
        (valid[b] == 1)
        & (src[b] == key_src[:, None])
        & (dst[b] == key_dst[:, None])
        & (ports[b] == key_ports[:, None])
        & (proto[b] == key_proto[:, None])
        & (now - time[b] <= max_age)
    )
    found = jnp.any(match, axis=1)
    return found, jnp.argmax(match, axis=1).astype(jnp.int32)


def _sess_probe_dispatch(tables, b, key_src, key_dst, key_ports,
                         key_proto, now, max_age):
    """(found, first-way) via the fused kernel on a TPU backend, the
    gather rung elsewhere — the mxu_classify_columns dispatch shape.
    Callers pass ``now=0, max_age=_BIG`` to express "no age check"
    (time is a non-negative tick counter, so the condition is
    vacuous)."""
    from vpp_tpu.ops._pallas import use_pallas

    if use_pallas():
        return sess_probe_ways(
            b, key_src, key_dst, key_ports, key_proto,
            tables.sess_valid, tables.sess_src, tables.sess_dst,
            tables.sess_ports, tables.sess_proto, tables.sess_time,
            now, max_age)
    return _probe_ways_reference(
        b, key_src, key_dst, key_ports, key_proto,
        tables.sess_valid, tables.sess_src, tables.sess_dst,
        tables.sess_ports, tables.sess_proto, tables.sess_time,
        now, max_age)
