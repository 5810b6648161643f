"""Device-resident telemetry plane (ISSUE 11 tentpole).

nanoPU (PAPERS.md) argues the metric that matters for reflex workloads
is wire-to-wire TAIL latency, yet until this round the only latency
numbers were host-side medians sampled around whole benchmark runs —
nothing per-packet, nothing under load, nothing a latency governor
(ROADMAP item 3) could close a loop on. This module puts the
measurement substrate INSIDE the fused step:

* **wire-latency histogram** — the pump stamps an rx-enqueue timestamp
  (microseconds, ``tel_clock_us``) into a spare descriptor lane at
  staging; the packed boundary computes ``now_us − rx_stamp`` at
  tx-append and scatter-adds each packet into a device-resident
  log2-bucket histogram plane. Bucket ``b`` counts latencies in
  ``[2^b, 2^(b+1)) µs`` (bucket 0 additionally covers 0..1 µs, the
  last bucket saturates). The bucketing is EXACT integer math — a
  compare-and-sum against the power-of-two thresholds — so a NumPy
  recompute over the same latencies reproduces the bins bit-for-bit
  (tests/test_telemetry.py pins this).
* **heavy-hitter flow sketch** — a count-min sketch (``d`` hash rows ×
  ``w`` counters, the session table's multiplicative-xor hash family
  salted per row) updated by scatter-add in the same step, plus a
  small top-K candidate table elected one leader per step (the PR-6
  rep-ranking idea collapsed to the K-entry regime: resident keys
  refresh to the batch max estimate, the best non-resident flow
  challenges the minimum-count slot). ``show top-flows`` names the
  flows behind a latency spike or DDoS flag WITHOUT ever shipping the
  session table — only the K candidate rows and the histogram bins
  cross the transport at collect time; the [d, w] sketch itself stays
  device-resident.

Both structures ride the ``DataplaneTables`` pytree like the sweep
cursors: the step returns updated planes, epoch swaps carry them by
reference, and the persistent ring threads them window-to-window. On
the ring path the accumulated bins travel back as a widened aux rider
in the window's ONE existing result fetch (``pack_tel_rider``), so
``io_callbacks`` stays 0 by construction.

Knob-gated (``dataplane.telemetry: off | latency | full``): "off"
carries minimal placeholder shapes and compiles the stage out entirely
(the ml_stage pattern — signatures and jit keys of the off state are
byte-identical to the pre-telemetry programs); "latency" enables the
histogram only; "full" adds the flow sketch + top-K.

Count-min error bound (docs/OBSERVABILITY.md has the math): every
estimate over-counts, never under-counts; with width ``w`` and depth
``d`` the overestimate of any flow exceeds ``e·N/w`` (N = packets
sketched) with probability at most ``e^-d``.
"""

from __future__ import annotations

import time
from typing import Dict, Tuple

import numpy as np

import jax.numpy as jnp

# telemetry knob values (DataplaneConfig.telemetry)
TEL_MODES = ("off", "latency", "full")

# geometry defaults, mirrored by DataplaneConfig
TEL_LAT_BUCKETS_DEFAULT = 24   # log2 µs buckets: 1 µs .. ~8.4 s
TEL_SKETCH_ROWS_DEFAULT = 2    # count-min depth d
TEL_SKETCH_COLS_DEFAULT = 1024  # count-min width w (power of two)
TEL_TOPK_DEFAULT = 8           # heavy-hitter candidate slots

# per-row salts of the sketch hash family: the session table's
# multiplicative-xor scheme (ops/session.py _hash / ops/mlscore.py
# _flow_hash), re-mixed per row with a distinct odd constant so the d
# rows are pairwise-independent enough for the count-min bound
_ROW_SALTS = (0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F,
              0x165667B1, 0xD3A2646D, 0xFD7046C5, 0xB55A4F09)


def tel_clock_us() -> int:
    """Monotonic microseconds wrapped to a positive int32 — the shared
    clock of the rx-enqueue stamps and the dispatch-time ``now_us``.
    Wrap (every ~35.8 min) makes a latency read negative, and negative
    latencies are simply not observed (the caller's observe mask), so
    a wrap costs one window of samples, never a corrupt bucket."""
    return int(time.monotonic() * 1e6) & 0x7FFFFFFF


# --- wire-latency histogram -------------------------------------------

def lat_bucket(lat_us: jnp.ndarray, n_buckets: int) -> jnp.ndarray:
    """Exact log2 bucket index of each latency: the count of
    power-of-two thresholds ``2^k`` (k = 1..n_buckets-1) at or below
    the value. Pure integer compares — no float log, so the NumPy
    oracle reproduces it bit-for-bit (floor(log2(x)) via jnp.log2
    mis-buckets values adjacent to powers of two)."""
    thresholds = jnp.asarray([1 << k for k in range(1, n_buckets)],
                             jnp.int32)
    return jnp.sum(
        (lat_us[:, None] >= thresholds[None, :]).astype(jnp.int32),
        axis=1)


def lat_bucket_np(lat_us: np.ndarray, n_buckets: int) -> np.ndarray:
    """The independent host-side twin of ``lat_bucket`` (differential
    tests)."""
    thresholds = np.asarray([1 << k for k in range(1, n_buckets)],
                            np.int64)
    return (np.asarray(lat_us, np.int64)[:, None]
            >= thresholds[None, :]).sum(axis=1).astype(np.int32)


def tel_latency_update(tables, observe: jnp.ndarray,
                       lat_us: jnp.ndarray):
    """Scatter one batch's wire latencies into the device histogram.

    ``observe`` [P] masks which packets count (valid, stamped, and a
    non-negative latency — the caller builds it); ``lat_us`` [P] is
    clamped at 0 so a masked-out lane can never index out of range.
    Returns ``(tables', n_observed)``."""
    nb = tables.tel_lat_hist.shape[0]
    lat = jnp.maximum(lat_us, 0)
    inc = observe.astype(jnp.int32)
    hist = tables.tel_lat_hist.at[lat_bucket(lat, nb)].add(inc)
    return tables._replace(tel_lat_hist=hist), jnp.sum(inc)


# --- heavy-hitter flow sketch ----------------------------------------

def tel_flow_hash(pkts) -> jnp.ndarray:
    """Base per-flow hash — the session table's multiplicative-xor
    family (ops/session.py _hash) on the post-NAT-reverse header. The
    ONE device copy: ops/mlscore.py's rate-limit gate aliases this
    function, so a flow hashes identically here and in the ML
    ratelimit gate by construction (not by parallel maintenance)."""
    h = pkts.src_ip * jnp.uint32(0x9E3779B1)
    h = h ^ (pkts.dst_ip * jnp.uint32(0x85EBCA77))
    ports = ((pkts.sport.astype(jnp.uint32) << 16)
             | (pkts.dport.astype(jnp.uint32) & 0xFFFF))
    h = h ^ (ports * jnp.uint32(0xC2B2AE3D))
    h = h ^ (pkts.proto.astype(jnp.uint32) * jnp.uint32(0x27D4EB2F))
    return h ^ (h >> 15)


def tel_flow_hash_np(src, dst, sport, dport, proto) -> np.ndarray:
    """Host twin of ``tel_flow_hash`` (oracle + CLI flow naming)."""
    u = np.uint32
    with np.errstate(over="ignore"):
        h = np.asarray(src, u) * u(0x9E3779B1)
        h = h ^ (np.asarray(dst, u) * u(0x85EBCA77))
        ports = ((np.asarray(sport, np.uint64).astype(u) << u(16))
                 | (np.asarray(dport, u) & u(0xFFFF)))
        h = h ^ (ports * u(0xC2B2AE3D))
        h = h ^ (np.asarray(proto, u) * u(0x27D4EB2F))
    return h ^ (h >> u(15))


def sketch_cols(h0, row: int, w: int):
    """Column of base hash ``h0`` in sketch row ``row`` (works on jnp
    AND np uint32 arrays — one copy of the per-row mix, so the device
    kernel and the host oracle cannot drift)."""
    # jax-ok: a static TYPE dispatch (np oracle vs device path), not a
    # branch on a tracer's value — the chosen arm is fixed per caller
    if isinstance(h0, np.ndarray):
        u = np.uint32
        with np.errstate(over="ignore"):
            hr = h0 * u(_ROW_SALTS[row % len(_ROW_SALTS)])
        hr = hr ^ (hr >> u(13))
        return (hr & u(w - 1)).astype(np.int32)
    hr = h0 * jnp.uint32(_ROW_SALTS[row % len(_ROW_SALTS)])
    hr = hr ^ (hr >> 13)
    return (hr & jnp.uint32(w - 1)).astype(jnp.int32)


def tel_flow_update(tables, pkts, alive: jnp.ndarray):
    """One step's count-min + top-K update (telemetry "full" only —
    the step factory compiles this out below that).

    Sketch: one scatter-add per row (duplicate columns within the
    batch accumulate — ``.at[].add`` semantics). Estimates are the
    post-update per-row minimum (the standard CM query), so a flow's
    estimate never under-counts.

    Top-K election, one round (the PR-6 rep-ranking toolbox collapsed
    to K slots): resident keys refresh their count to the batch's max
    estimate of the same key; the best NON-resident flow of the batch
    (first argmax — jnp and numpy agree on tie order) challenges the
    minimum-count slot and wins iff strictly larger (free slots hold
    count 0 and lose to any real flow). One insert per step amortizes
    exactly like the session sweep: heavy hitters recur across steps,
    so the table converges on them while mice never displace a
    resident elephant. Returns ``(tables', n_sketched)``."""
    d, w = tables.tel_sketch.shape
    k = tables.tel_top_key.shape[0]
    h0 = tel_flow_hash(pkts)
    inc = alive.astype(jnp.int32)
    sketch = tables.tel_sketch
    cols = [sketch_cols(h0, r, w) for r in range(d)]
    for r in range(d):
        sketch = sketch.at[r, cols[r]].add(inc)
    est = sketch[0, cols[0]]
    for r in range(1, d):
        est = jnp.minimum(est, sketch[r, cols[r]])
    est = jnp.where(alive, est, 0)

    key, cnt = tables.tel_top_key, tables.tel_top_cnt
    resident = cnt > 0
    match = (resident[:, None] & alive[None, :]
             & (key[:, None] == h0[None, :]))          # [K, P]
    cnt = jnp.maximum(cnt, jnp.max(
        jnp.where(match, est[None, :], 0), axis=1))
    in_table = jnp.any(match, axis=0)
    cand = jnp.where(alive & ~in_table, est, -1)
    lead = jnp.argmax(cand).astype(jnp.int32)
    lead_est = cand[lead]
    vic = jnp.argmin(cnt).astype(jnp.int32)
    sel = (jnp.arange(k, dtype=jnp.int32) == vic) & (lead_est > cnt[vic])
    tables = tables._replace(
        tel_sketch=sketch,
        tel_top_key=jnp.where(sel, h0[lead], key),
        tel_top_src=jnp.where(sel, pkts.src_ip[lead], tables.tel_top_src),
        tel_top_dst=jnp.where(sel, pkts.dst_ip[lead], tables.tel_top_dst),
        tel_top_ports=jnp.where(
            sel,
            ((pkts.sport[lead].astype(jnp.uint32) << 16)
             | (pkts.dport[lead].astype(jnp.uint32) & 0xFFFF)),
            tables.tel_top_ports),
        tel_top_cnt=jnp.where(sel, lead_est, cnt),
        tel_sketched=tables.tel_sketched + jnp.sum(inc),
    )
    return tables, jnp.sum(inc)


# --- the ring aux rider ----------------------------------------------

def tel_rider_width(nb: int, k: int) -> int:
    """int32 words of the packed telemetry rider: the histogram bins,
    the sketched-packet scalar, and the 5 top-K candidate planes."""
    return nb + 1 + 5 * k


def pack_tel_rider(tables) -> jnp.ndarray:
    """Flatten the host-facing telemetry planes into ONE int32 vector
    that rides the ring window's existing result fetch (the aux-rider
    pattern widened — ISSUE 11). Excludes the [d, w] sketch: only the
    bins + candidates cross the transport, never the sketch matrix."""
    from jax import lax

    def i32(x):
        return lax.bitcast_convert_type(x, jnp.int32)

    return jnp.concatenate([
        tables.tel_lat_hist,
        tables.tel_sketched[None],
        i32(tables.tel_top_key),
        i32(tables.tel_top_src),
        i32(tables.tel_top_dst),
        i32(tables.tel_top_ports),
        tables.tel_top_cnt,
    ])


def unpack_tel_rider(raw: np.ndarray, nb: int, k: int) -> Dict[str, np.ndarray]:
    """Host inverse of ``pack_tel_rider`` (geometry from the config —
    tables.tel_capacity)."""
    raw = np.asarray(raw, np.int32)
    assert raw.shape[0] == tel_rider_width(nb, k), raw.shape
    off = nb + 1
    u = np.uint32

    def plane(i):
        return raw[off + i * k: off + (i + 1) * k]

    return {
        "bins": raw[:nb].copy(),
        "sketched": int(raw[nb]),
        "top_key": plane(0).view(u),
        "top_src": plane(1).view(u),
        "top_dst": plane(2).view(u),
        "top_ports": plane(3).view(u),
        "top_cnt": plane(4).copy(),
    }


# --- host-side derivations (collect-time; no device work) -------------

def bucket_bounds_seconds(nb: int) -> Tuple[float, ...]:
    """Prometheus ``le`` bounds of the device bins, in SECONDS: device
    bucket b covers [2^b, 2^(b+1)) µs, so its upper bound is
    2^(b+1) µs; the saturating last bucket maps to +Inf (implicit).
    Strictly increasing by construction — the --metrics lint checks."""
    return tuple((1 << (b + 1)) / 1e6 for b in range(nb - 1))


def quantiles_from_bins(bins: np.ndarray,
                        qs=(0.5, 0.99, 0.999)) -> Tuple[float, ...]:
    """Percentiles (µs) from the log2 bins, linearly interpolated
    within the winning bucket (docs/OBSERVABILITY.md has the math).
    All-zero bins yield 0.0 — 'no data', not 'zero latency'."""
    bins = np.asarray(bins, np.int64)
    total = int(bins.sum())
    if total == 0:
        return tuple(0.0 for _ in qs)
    cum = np.cumsum(bins)
    out = []
    for q in qs:
        rank = q * total
        b = int(np.searchsorted(cum, rank, side="left"))
        b = min(b, len(bins) - 1)
        lo = float(1 << b) if b else 0.0
        hi = float(1 << (b + 1))
        prev = int(cum[b - 1]) if b else 0
        frac = (rank - prev) / max(int(bins[b]), 1)
        out.append(lo + (hi - lo) * min(max(frac, 0.0), 1.0))
    return tuple(out)


def approx_sum_us(bins: np.ndarray) -> float:
    """Lower-bound latency sum for the histogram's ``_sum`` series:
    each bucket contributes its TRUE lower bound — 2^b µs, and 0 for
    bucket 0 (it covers [0, 2) µs, so crediting anything would break
    the lower-bound property for sub-microsecond samples). Documented
    approximation — the exact sum never crosses the transport, and
    ``_sum`` only has to stay monotone, which cumulative bins
    guarantee."""
    bins = np.asarray(bins, np.int64)
    reps = np.asarray([(1 << b) if b else 0 for b in range(len(bins))],
                      np.int64)
    return float((bins * reps).sum())
