"""The generator of each configuration's mixes, on the CPU: the same seed
gives the same packets, new flows never repeat, the mix shares hold, and
the wire bytes parse back (through the program's codec) to the fields."""

import json
from pathlib import Path

import numpy as np
import pytest

from benchmark.gen import FILL_BASE, Generator, seed_key

ROOT = Path(__file__).resolve().parents[2]
BIG_SEED = 2**31 + 12345


def world(n_pods=110, peers=4999):
    return {"uplink_if": 0, "gateway": 0,
            "pod_ip": [(10 << 24) + (1 << 16) + (1 << 8) + 2 + k
                       for k in range(n_pods)],
            "pod_if": list(range(1, n_pods + 1)),
            "peer_nodes": [i for i in range(peers + 1) if i != 1],
            "node_net_base": (10 << 24) + (1 << 16),
            "vip": ((10 << 24) + (96 << 16) + 10, 80),
            "outside_blocks": {"base": (172 << 24) + (16 << 16),
                               "count": 1000, "hosts": 254}}


def mix(name):
    return json.loads((ROOT / "benchmark/traffic" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("name", ["newflow.sat", "newflow.paced",
                                  "64B.sat"])
def test_same_seed_same_frames(name):
    a = Generator(mix(name), world(), BIG_SEED)
    b = Generator(mix(name), world(), BIG_SEED)
    c = Generator(mix(name), world(), BIG_SEED + 1)
    k = np.arange(5, 9)
    fa, fb, fc = a.frame_fields(k), b.frame_fields(k), c.frame_fields(k)
    for col in fa:
        assert np.array_equal(fa[col], fb[col]), col
    assert not np.array_equal(fa["src_ip"], fc["src_ip"]) \
        or not np.array_equal(fa["sport"], fc["sport"])
    assert np.array_equal(a.wire(fa), b.wire(fb))


def test_new_flows_are_unique_and_shares_hold():
    g = Generator(mix("newflow.sat"), world(), -7)
    f = g.fields(np.concatenate([np.arange(200_000, dtype=np.uint64),
                                 FILL_BASE + np.arange(50_000,
                                                       dtype=np.uint64)]))
    tup = (f["src_ip"].astype(np.uint64) << np.uint64(16)) \
        | f["sport"].astype(np.uint64)
    assert len(np.unique(tup)) == len(tup)
    share = np.bincount(f["kind"], minlength=3) / len(f["kind"])
    assert share == pytest.approx([0.70, 0.15, 0.15], abs=0.01)
    assert (f["dport"][f["kind"] == 2] == 80).all()
    assert ((f["src_ip"] >> 20) == (172 << 4) + 1).all()  # 172.16/12


def test_pod2pod_flows():
    g = Generator(mix("64B.sat"), world(peers=0), 99)
    f = g.fields(np.arange(100_000, dtype=np.uint64))
    pods = np.asarray(world()["pod_ip"], np.uint32)
    assert (f["kind"] == 0).all()
    assert np.isin(f["dst_ip"], pods).all()
    assert (f["src_ip"] != f["dst_ip"]).all()
    assert len(np.unique(f["sport"])) == 1024
    assert (f["rx_if"] == np.asarray(world()["pod_if"])[f["src_pod"]]).all()


@pytest.mark.parametrize("name", ["newflow.sat", "64B.sat"])
def test_wire_bytes_parse_back(name):
    from vpp_tpu.native.pktio import PacketCodec

    g = Generator(mix(name), world(), BIG_SEED)
    f = g.frame_fields(np.array([3]))
    rows = g.wire(f)
    assert rows.shape == (g.frame_pkts, g.frame_bytes)
    codec = PacketCodec(snap=g.frame_bytes)
    cols, n = codec.parse_inplace(rows, np.full(g.frame_pkts, g.frame_bytes,
                                                np.uint32), g.frame_pkts, 0)
    assert n == g.frame_pkts
    for c in ("src_ip", "dst_ip", "proto", "sport", "dport", "ttl",
              "pkt_len"):
        assert np.array_equal(cols[c][:n].astype(np.int64),
                              f[c].astype(np.int64)), c
    # valid IPv4 header checksums: the one's-complement sum folds to 0
    hdr = rows[:, 14:34].copy().view(">u2").astype(np.uint32).sum(axis=1)
    while (hdr >> 16).any():
        hdr = (hdr & 0xFFFF) + (hdr >> 16)
    assert (hdr == 0xFFFF).all()


def test_poisson_arrivals_are_seeded():
    m = dict(mix("newflow.paced"), rate_fps=1000)
    a = Generator(m, world(), 5).due_times(10.0)
    b = Generator(m, world(), 6).due_times(10.0)
    assert np.array_equal(a, Generator(m, world(), 5).due_times(10.0))
    assert len(a) == pytest.approx(10_000, rel=0.01)
    assert (np.diff(a) > 0).all() and a[-1] < 10.0
    # another seed: the same gaps in another order
    assert not np.array_equal(a, b)
    ga, gb = (np.sort(np.diff(t, prepend=0.0)) for t in (a, b))
    n = min(len(ga), len(gb))
    assert abs(len(ga) - len(gb)) <= 2
    assert np.allclose(ga[:n - 2], gb[:n - 2])
    assert Generator(mix("newflow.sat"), world(), 5).due_times(10.0) is None


def test_seed_key_takes_any_whole_number():
    assert seed_key(2**40) != seed_key(2**40 + 1)
    assert isinstance(seed_key(-1), int)
