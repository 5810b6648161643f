"""The comparison that decides ``correct``: what the timed path produced,
against the configuration's plain reference.

After the window has closed and the program's state is freed, the
frames the check sampled (a seeded draw over every frame id, kept as
they left the tx ring) are regenerated from the seed and run through
the reference. A packet is wrong when any tx-ring column differs:
addresses and ports (the DNAT rewrite), protocol, TTL, disposition
(the policy verdict and the FIB decision), egress interface and next
hop. Comparing position by position also checks packet order.

Where the configuration has a VIP, the check also probes the state the
window left behind. The window's last frames are kept (``tail_frames``
in the mix); once the window has closed, each of their VIP flows that
was forwarded gets its backend's reply (a SYN-ACK from the backend pod)
and then its client's second packet (an ACK), served through the same
path. A reply finds the flow's NAT session only if the program stored
it at its SYN, with the backend it chose, and did not lose it; a second
packet goes to the backend its SYN got only if the choice holds per
flow. The reference keeps both without bound, so only flows with few
newer ones are probed: the last frames' flows.

Numbers compared, each with its limit:

- ``wrong_pkts``: sampled packets whose output differs from the
  reference (exact, limit 0);
- ``lost_pkts``: packets pushed into the rx ring that never came back
  through the tx ring (exact, limit 0);
- ``bad_frames``: frames that came back with another packet count than
  they were pushed with, or that no pushed frame accounts for (exact,
  limit 0);
- ``flow_wrong_pkts``: probe packets (replies and second packets) whose
  output differs from the reference or that never came back (exact,
  limit 0);
- ``backend_weight_gap_pct``: over the sampled VIP packets, the largest
  gap, in percentage points, between the share that went to backends
  of one weight and the share the weights give them;
- ``backend_chi2``: Pearson's chi-square of the sampled VIP packets per
  backend against the weights, over its degrees of freedom (about 1
  when the picks follow the weights).

The last two are statistics of a sample; their limits are set from the
readings in PERF.md.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from benchmark.gen import hash64
from benchmark.served import Load

COMPARE = ("src_ip", "dst_ip", "proto", "sport", "dport", "ttl", "disp",
           "rx_if", "next_hop")
LIMITS = {"wrong_pkts": 0, "lost_pkts": 0, "bad_frames": 0,
          "flow_wrong_pkts": 0, "backend_weight_gap_pct": 12.0,
          "backend_chi2": 4.0}
VIP_KIND = 2                     # the generator's ``kind`` of a VIP packet
DROP = 0                         # the tx ring's disposition of a drop
TCP_SYN_ACK, TCP_ACK = 0x12, 0x10


def sample_ids(load: Load, key: int, n: int) -> List[int]:
    """Up to ``n`` kept frames that came back, in a seeded order."""
    ids = np.array(sorted(k for k in load.kept if k in load.got),
                   np.uint64)
    if not len(ids):
        return []
    order = np.argsort(hash64(key, ids, 131), kind="stable")
    return sorted(int(k) for k in ids[order][:n])


def served_columns(load: Load, ids: List[int]) -> Dict[str, np.ndarray]:
    return {c: np.concatenate([load.kept[k][c] for k in ids]).astype(np.int64)
            for c in COMPARE}


def compare(ref, gen, load: Load, ids: List[int],
            control: Optional[object] = None) -> Dict:
    """Numbers of the check. ``ref`` is the configuration's Reference;
    with ``control`` (a Reference that breaks one guarantee) its answers
    stand in for what the program served, as the control run does."""
    fp = gen.frame_pkts
    full = [k for k in ids if len(load.kept[k]["disp"]) == fp]
    lost = sum(n for k, (_t, n) in load.pushed.items() if k not in load.got)
    bad = load.stray + sum(1 for k, (_t, n) in load.got.items()
                           if n != load.pushed[k][1])
    wrong = 0
    checked = 0
    cols_bad = {}
    if full:
        f = gen.frame_fields(np.array(full, np.uint64))
        if control is not None:
            served = control.expected(f)
            served = {c: np.asarray(served[c], np.int64) for c in COMPARE}
        else:
            served = served_columns(load, full)
        want = ref.expected(f, served_dst=served["dst_ip"])
        diff = np.zeros(len(f["src_ip"]), bool)
        for c in COMPARE:
            bad_c = np.asarray(want[c], np.int64) != served[c]
            if bad_c.any():
                j = np.nonzero(bad_c)[0][:3]
                cols_bad[c] = {"n": int(bad_c.sum()),
                               "kind": f["kind"][j].tolist(),
                               "want": np.asarray(want[c])[j].tolist(),
                               "got": served[c][j].tolist()}
            diff |= bad_c
        wrong = int(diff.sum())
        checked = int(len(diff))
    numbers = {"wrong_pkts": wrong, "lost_pkts": int(lost),
               "bad_frames": int(bad)}
    if full and control is None:
        numbers.update(backend_spread(ref, f, served))
    return {"numbers": numbers,
            "checked_pkts": checked, "checked_frames": len(full),
            "columns": cols_bad, "stray_frames": load.stray,
            "kinds": _kinds(gen, full)}


def backend_spread(ref, f: Dict[str, np.ndarray],
                   served: Dict[str, np.ndarray]) -> Dict[str, float]:
    """How the sampled VIP packets spread over the backends, against
    the weights: ``backend_weight_gap_pct`` and ``backend_chi2``. Empty
    where the configuration has no VIP."""
    w = ref.backend_weights()
    if w is None:
        return {}
    backends = ref.vip[3]
    sel = (f["kind"] == VIP_KIND) & (served["disp"] != DROP)
    idx = {int(b): i for i, b in enumerate(backends)}
    hits = np.array([idx[d] for d in served["dst_ip"][sel].tolist()
                     if d in idx], np.int64)
    if not len(hits):
        return {}
    count = np.bincount(hits, minlength=len(w)).astype(np.float64)
    due = w / w.sum()
    gap = max(abs(count[w == v].sum() / len(hits) - due[w == v].sum())
              for v in np.unique(w))
    exp = due * len(hits)
    chi2 = float(((count - exp) ** 2 / exp).sum()) / (len(w) - 1)
    return {"backend_weight_gap_pct": round(100.0 * float(gap), 4),
            "backend_chi2": round(chi2, 4)}


def flow_probe(load: Load, gen, world: Dict) -> None:
    """Serve the probe of the window's last VIP flows (module doc):
    every backend's reply, then every client's second packet. Runs once
    the window has closed, before the program stops; the frame ids land
    in ``load.probe``."""
    load.probe = None
    if not load.tail or not world.get("vip", (0, 0))[0]:
        return
    ks = np.array([k for k, _ in load.tail], np.uint64)
    f = gen.frame_fields(ks)
    served = {c: np.concatenate([cols[c] for _, cols in load.tail])
              for c in load.tail[0][1]}
    if len(served["disp"]) != len(f["kind"]):
        # a short frame (bad_frames counts it): nothing can be probed
        load.probe = {"broken": len(f["kind"])}
        return
    pod_if = dict(zip((int(a) for a in world["pod_ip"]),
                      (int(i) for i in world["pod_if"])))
    # forwarded VIP flows whose backend is a pod (another backend is
    # wrong, and the comparison of the tail below counts it)
    sel = ((f["kind"] == VIP_KIND) & (served["disp"] != DROP)
           & np.isin(served["dst_ip"].astype(np.int64), list(pod_if)))
    dst = served["dst_ip"][sel].astype(np.int64)
    syn = {c: v[sel] for c, v in f.items()}
    reply = dict(syn,
                 src_ip=dst.astype(np.uint32),
                 dst_ip=syn["src_ip"],
                 sport=served["dport"][sel].astype(np.int32),
                 dport=syn["sport"],
                 rx_if=np.array([pod_if[int(d)] for d in dst], np.int32))
    load.probe = {"tail": (f, served),
                  "reply": (reply, load.serve_fields(reply, TCP_SYN_ACK)),
                  "second": (syn, load.serve_fields(syn, TCP_ACK))}


def compare_flows(ref, load: Load) -> Dict:
    """``flow_wrong_pkts`` of the probe against the reference, which
    first sees the window's last frames with the backends they got (the
    kept columns of those frames are compared too)."""
    probe = getattr(load, "probe", None)
    if probe is None:
        return {"numbers": {}, "probe_pkts": 0, "columns": {}}
    if "broken" in probe:
        return {"numbers": {"flow_wrong_pkts": probe["broken"]},
                "probe_pkts": 0, "columns": {"tail": "short frames"}}
    f, served = probe["tail"]
    want = ref.expected(f, served_dst=served["dst_ip"].astype(np.int64))
    cols_bad = {}
    diff = np.zeros(len(f["src_ip"]), bool)
    for c in served:
        bad_c = np.asarray(want[c], np.int64) != served[c].astype(np.int64)
        if bad_c.any():
            cols_bad[f"tail.{c}"] = int(bad_c.sum())
        diff |= bad_c
    wrong = int(diff.sum())
    total = len(diff)
    for name in ("reply", "second"):
        f, ids = probe[name]
        if not len(f["src_ip"]):
            continue
        want = ref.expected(f)
        total += len(f["src_ip"])
        got = [load.probe_got.get(k) for k in ids]
        if len(ids) * load.feed.fp < len(f["src_ip"]) or any(
                g is None for g in got):
            # frames never pushed or never back: every packet is wrong
            wrong += len(f["src_ip"])
            cols_bad[name] = "missing frames"
            continue
        served = {c: np.concatenate([g[c] for g in got]).astype(np.int64)
                  for c in COMPARE}
        if len(served["disp"]) != len(f["src_ip"]):
            wrong += len(f["src_ip"])
            cols_bad[name] = "packet count"
            continue
        diff = np.zeros(len(f["src_ip"]), bool)
        for c in COMPARE:
            bad_c = np.asarray(want[c], np.int64) != served[c]
            if bad_c.any():
                j = np.nonzero(bad_c)[0][:3]
                cols_bad[f"{name}.{c}"] = {
                    "n": int(bad_c.sum()),
                    "want": np.asarray(want[c])[j].tolist(),
                    "got": served[c][j].tolist()}
            diff |= bad_c
        wrong += int(diff.sum())
    return {"numbers": {"flow_wrong_pkts": wrong}, "probe_pkts": total,
            "columns": cols_bad}


def _kinds(gen, ids: List[int]) -> Dict[str, int]:
    if not ids:
        return {}
    k = gen.frame_fields(np.array(ids, np.uint64))["kind"]
    return {name: int((k == i).sum())
            for i, name in enumerate(("local_pod", "peer_pod", "vip"))}


def verdict(numbers: Dict[str, float]) -> bool:
    """Every number within its limit; the three numbers every cell has
    must be there."""
    return (all(k in numbers for k in ("wrong_pkts", "lost_pkts",
                                       "bad_frames"))
            and all(v <= LIMITS[k] for k, v in numbers.items()))
