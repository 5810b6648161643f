"""ip4-lookup: vectorized longest-prefix-match over the FIB.

Reference analog: VPP's mtrie-based ip4-lookup node. Two device
implementations share this module's slot RESOLVER (so they can never
diverge on route semantics) and return the same ``FibResult``:

* **dense** (here): the whole FIB is matched [P packets] x [F routes]
  masked-compare, longest matching prefix wins via argmax on prefix
  length. O(P*F) — simpler AND faster at node-route scale (pod /32s,
  subnets, default: tens of entries).
* **lpm** (vpp_tpu.ops.lpm): per-prefix-length sorted prefix planes,
  one ``searchsorted`` + exact-match gather per populated length —
  O(P * lengths * log N). The internet-scale path (ISSUE 15): a full
  BGP feed is ~1M prefixes, where the dense compare is 4 orders of
  magnitude too much arithmetic (and an O(P*F) intermediate that does
  not even fit memory).

The selection ladder (``dataplane.fib_impl: dense | lpm | auto``) is
re-gated at every epoch swap exactly like the classifier ladder
(pipeline/dataplane.py ``_refresh_selection``; docs/ROUTING.md).

ECMP (ISSUE 15): a route may resolve to a next-hop GROUP instead of
its scalar next-hop columns — ``fib_grp[slot] >= 0`` names a
``[G, W]`` member table and the member is picked by the session flow
hash (ops/session.py ``_hash_mix`` — the SAME hash family the session
table buckets with, so a flow's member choice is deterministic and
sticky: member churn only moves flows whose way slot was reassigned,
pipeline/tables.py ``set_nh_group``). An EMPTY group (0 members
staged) fails closed as a no-route drop — misdelivering to a stale
member is worse than dropping until the group is staged.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from vpp_tpu.ops.session import _hash_mix, _pack_ports
from vpp_tpu.pipeline.tables import DataplaneTables
from vpp_tpu.pipeline.vector import Disposition, PacketVector


class FibResult(NamedTuple):
    matched: jnp.ndarray    # bool [P] — a route exists
    tx_if: jnp.ndarray      # int32 [P]
    disp: jnp.ndarray       # int32 [P] Disposition (DROP when unmatched)
    next_hop: jnp.ndarray   # uint32 [P]
    node_id: jnp.ndarray    # int32 [P] remote node index, -1 local
    snat: jnp.ndarray       # bool [P] route is marked for source-NAT
    grp: jnp.ndarray        # int32 [P] ECMP group serving the packet,
    #                         -1 = unicast route (scalar next-hop)
    way: jnp.ndarray        # int32 [P] member slot picked by the flow
    #                         hash (0 when grp == -1) — grp/way feed the
    #                         per-member vpp_tpu_fib_ecmp_* accounting
    #                         plane in graph._finish_step


def fib_flow_mix(pkts: PacketVector) -> jnp.ndarray:
    """The ECMP member-selection hash [P] (uint32): the session
    table's multiplicative-xor 5-tuple mix (ops/session.py), reused
    verbatim so a flow's member pick is exactly as sticky as its
    session bucket — one hash family to reason about, one set of
    avalanche properties (docs/ROUTING.md "ECMP hash contract")."""
    return _hash_mix(pkts.src_ip, pkts.dst_ip,
                     _pack_ports(pkts.sport, pkts.dport), pkts.proto)


def resolve_fib_slot(tables: DataplaneTables, slot: jnp.ndarray,
                     matched: jnp.ndarray,
                     mix: jnp.ndarray) -> FibResult:
    """Resolve matched FIB slots [P] to forwarding data — THE shared
    tail of every lookup implementation (dense and LPM call this with
    their own (slot, matched); route semantics can't diverge).

    Unicast slots read the per-slot scalar columns; ECMP slots
    (``fib_grp[slot] >= 0``) read member ``way = mix & (W-1)`` of the
    group's ``[G, W]`` tables. W is a power of two (validated) so the
    mask IS the modulo. An empty group (``fib_grp_n == 0``) fails
    closed: the packet resolves unmatched (no-route attribution)."""
    safe = jnp.where(matched, slot, 0)
    tx_if = tables.fib_tx_if[safe]
    disp = tables.fib_disp[safe]
    next_hop = tables.fib_next_hop[safe]
    node_id = tables.fib_node_id[safe]
    snat = tables.fib_snat[safe]
    g = tables.fib_grp[safe]
    n_grp, ways = tables.fib_grp_nh.shape
    way = (mix & jnp.uint32(ways - 1)).astype(jnp.int32)
    gs = jnp.clip(g, 0, n_grp - 1)
    is_grp = matched & (g >= 0)
    live = is_grp & (tables.fib_grp_n[gs] > 0)
    tx_if = jnp.where(live, tables.fib_grp_tx_if[gs, way], tx_if)
    next_hop = jnp.where(live, tables.fib_grp_nh[gs, way], next_hop)
    node_id = jnp.where(live, tables.fib_grp_node[gs, way], node_id)
    # empty group: fail closed as a no-route miss (never forward to a
    # zero next-hop), counted like any FIB miss
    matched = matched & (~is_grp | live)
    return FibResult(
        matched=matched,
        tx_if=jnp.where(matched, tx_if, -1),
        disp=jnp.where(matched, disp,
                       int(Disposition.DROP)).astype(jnp.int32),
        next_hop=jnp.where(matched, next_hop, jnp.uint32(0)),
        node_id=jnp.where(matched, node_id, -1),
        snat=matched & (snat == 1),
        grp=jnp.where(live, g, -1),
        way=jnp.where(live, way, 0),
    )


def _dense_match(tables: DataplaneTables, dst_ip: jnp.ndarray):
    """(matched [P], slot [P]) of the dense masked-compare: longest
    prefix wins, ties (duplicate prefixes) go to the LOWEST slot —
    the argmax-first-index semantics the LPM staging mirrors
    (pipeline/tables.py _restage_lpm keeps the lowest slot per
    duplicate prefix), so the two implementations are bit-exact."""
    hits = (dst_ip[:, None] & tables.fib_mask[None, :]) == \
        tables.fib_prefix[None, :]
    hits = hits & (tables.fib_plen[None, :] >= 0)
    score = jnp.where(hits, tables.fib_plen[None, :], -1)
    best = jnp.argmax(score, axis=1)
    matched = jnp.take_along_axis(score, best[:, None], axis=1)[:, 0] >= 0
    return matched, best.astype(jnp.int32)


def fib_lookup_dense(tables: DataplaneTables,
                     pkts: PacketVector) -> FibResult:
    """The dense ip4-lookup over a full packet vector (the ``fib_fn``
    the step factory composes for ``fib_impl: dense`` —
    pipeline/graph.py)."""
    matched, slot = _dense_match(tables, pkts.dst_ip)
    return resolve_fib_slot(tables, slot, matched, fib_flow_mix(pkts))


def ip4_lookup(tables: DataplaneTables, dst_ip: jnp.ndarray) -> FibResult:
    """Header-only legacy entry (direct tests): LPM
    lookup of ``dst_ip`` [P] against the FIB slots, dense form. With
    no 5-tuple available the ECMP member pick degrades to a zero flow
    mix (member way 0) — unicast routes are unaffected; callers on the
    packet path use ``fib_lookup_dense``/``fib_lookup_lpm``."""
    matched, slot = _dense_match(tables, dst_ip)
    return resolve_fib_slot(tables, slot, matched,
                            jnp.zeros_like(dst_ip))
