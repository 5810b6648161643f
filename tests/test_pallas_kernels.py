"""Differential suite for the Pallas kernel pass (ISSUE 16).

Holds each fused kernel (run in *interpret* mode so the suite is
tier-1 on the CPU harness) bit-exact against its jnp reference rung
and, where one exists, an independent NumPy oracle:

- ops/acl_bv.py  bv_first_set / bv_first_match_fused  vs  the
  _first_set_bit priority encode and a per-row Python bit-scan oracle;
- ops/session.py sess_probe_ways  vs  _probe_ways_reference with
  planted hits, expired entries and the no-age-check convention;
- ops/lpm.py     _fib_lookup_lpm_pallas  vs  fib_lookup_lpm and the
  NumPy LPM oracle (reused from tests/test_lpm.py) over staged tables;
- the CPU dispatch identities (the pallas-rung entry points ARE the
  jnp rungs off-TPU), the three selection ladders' pallas_ok bit, the
  config-time mesh rejection, the step-factory bit-exactness of a
  fully pallas-knobbed step, the VMEM fit gate and the PALLAS_KERNELS
  manifest lint (tools/analysis/registries.py, run from tier-1 here
  like the other registry passes).
"""

import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from vpp_tpu.ops._pallas import pallas_available, use_pallas
from vpp_tpu.ops.acl_bv import (
    BV_ENC_MISS,
    _first_set_bit,
    acl_classify_global_bv,
    acl_classify_global_pallas,
    acl_classify_local_bv,
    acl_classify_local_pallas,
    bv_first_match,
    bv_first_match_fused,
    bv_first_set,
)
from vpp_tpu.ops.lpm import (
    _fib_lookup_lpm_pallas,
    fib_lookup_lpm,
    fib_lookup_lpm_fused,
)
from vpp_tpu.ops.session import (
    _BIG,
    SESS_PALLAS_VMEM_BUDGET,
    _probe_ways_reference,
    sess_probe_ways,
    session_pallas_fits,
)
from vpp_tpu.parallel.partition import (
    select_fib_impl,
    select_impl,
    select_session_impl,
    validate_partitioning,
)
from vpp_tpu.pipeline.graph import make_pipeline_step
from vpp_tpu.pipeline.tables import (
    DataplaneConfig,
    InterfaceType,
    TableBuilder,
)

from test_acl_bv import _cfg as _acl_cfg
from test_acl_bv import random_packets, random_rules
from test_lpm import (
    NumpyLpmOracle,
    _cfg as _lpm_cfg,
    _probe_traffic,
    _random_table,
    assert_fib_equal,
)

REPO = Path(__file__).resolve().parents[1]
TOOLS = REPO / "tools"

if not pallas_available():  # the image bakes in jax with pallas
    pytest.skip("jax.experimental.pallas unavailable",
                allow_module_level=True)


# --- bv_first_set: fused word-AND + first-set-bit ---------------------


def _np_first_rule(words: np.ndarray) -> np.ndarray:
    """Independent per-row bit-scan oracle: lowest set bit across the
    word vector, -1 when none (pure Python ints, no jnp tricks)."""
    p, w = words.shape
    out = np.full(p, -1, np.int64)
    for i in range(p):
        for j in range(w):
            v = int(words[i, j])
            if v:
                out[i] = j * 32 + ((v & -v).bit_length() - 1)
                break
    return out


@pytest.mark.parametrize("p,w,seed", [(1, 1, 0), (5, 3, 1), (300, 20, 2)])
def test_bv_first_set_matches_reference_and_oracle(p, w, seed):
    rng = np.random.default_rng(seed)
    rows = [rng.integers(0, 1 << 32, (p, w), dtype=np.uint32)
            for _ in range(5)]
    # sparsify so misses and single-bit survivors both occur; zeroing
    # one operand's row forces a guaranteed miss every third packet
    for r in rows[1:]:
        r &= rng.integers(0, 1 << 32, (p, w), dtype=np.uint32)
    for i in range(0, p, 3):
        rows[0][i] = 0
    jrows = [jnp.asarray(r) for r in rows]
    enc = np.asarray(bv_first_set(*jrows, interpret=True))

    combined = rows[0] & rows[1] & rows[2] & rows[3] & rows[4]
    matched, rule = _first_set_bit(jnp.asarray(combined))
    np.testing.assert_array_equal(enc != BV_ENC_MISS, np.asarray(matched))
    np.testing.assert_array_equal(
        np.where(enc != BV_ENC_MISS, enc, -1), np.asarray(rule))
    np.testing.assert_array_equal(
        np.where(enc != BV_ENC_MISS, enc, -1), _np_first_rule(combined))


@pytest.mark.parametrize("nrules", [1, 24])
def test_bv_first_match_fused_on_staged_tables(nrules):
    """Interpret-mode fused first-match over builder-committed BV
    planes agrees with bv_first_match on every packet (odd packet
    count exercises the tile padding)."""
    rng = np.random.default_rng(nrules)
    rules = random_rules(rng, nrules)
    b = TableBuilder(_acl_cfg())
    b.set_interface(1, InterfaceType.UPLINK, apply_global=True)
    b.set_global_table(rules)
    t = b.to_device()
    pkts = random_packets(rng, 257, rules)
    args = (t.glb_bv_bnd_src, t.glb_bv_bnd_dst, t.glb_bv_bnd_sport,
            t.glb_bv_bnd_dport, t.glb_bv_nbnd, t.glb_bv_src,
            t.glb_bv_dst, t.glb_bv_sport, t.glb_bv_dport,
            t.glb_bv_proto, pkts)
    m_ref, r_ref = bv_first_match(*args)
    m_fus, r_fus = bv_first_match_fused(*args, interpret=True)
    np.testing.assert_array_equal(np.asarray(m_fus), np.asarray(m_ref))
    np.testing.assert_array_equal(np.asarray(r_fus), np.asarray(r_ref))


def test_classify_pallas_is_bv_off_tpu():
    """The dispatch identity the safety net promises: on a non-TPU
    backend the pallas classify entry points ARE the bv rung —
    verdicts and rule indices identical, global and local."""
    assert not use_pallas()  # tier-1 runs on the CPU harness
    rng = np.random.default_rng(5)
    rules = random_rules(rng, 20)
    from test_acl_bv import _tables

    _, t = _tables(rules, rng=rng, n_local=2)
    pkts = random_packets(rng, 128, rules, max_if=4)
    for pal, ref in ((acl_classify_global_pallas, acl_classify_global_bv),
                     (acl_classify_local_pallas, acl_classify_local_bv)):
        vp, vr = pal(t, pkts), ref(t, pkts)
        np.testing.assert_array_equal(np.asarray(vp.permit),
                                      np.asarray(vr.permit))
        np.testing.assert_array_equal(np.asarray(vp.rule_idx),
                                      np.asarray(vr.rule_idx))


# --- sess_probe_ways: fused bucket probe + way election ---------------


def _sess_case(ways, seed, p=200, nb=32, plant=True, all_invalid=False):
    rng = np.random.default_rng(seed)
    valid = (rng.random((nb, ways)) < 0.5).astype(np.int32)
    src = rng.integers(0, 1 << 32, (nb, ways), dtype=np.uint32)
    dst = rng.integers(0, 1 << 32, (nb, ways), dtype=np.uint32)
    ports = rng.integers(0, 1 << 32, (nb, ways), dtype=np.uint32)
    proto = rng.integers(0, 256, (nb, ways)).astype(np.uint32)
    time = rng.integers(0, 1000, (nb, ways)).astype(np.int32)
    b = rng.integers(0, nb, p).astype(np.int32)
    key = [rng.integers(0, 1 << 32, p, dtype=np.uint32) for _ in range(3)]
    key.append(rng.integers(0, 256, p).astype(np.uint32))
    if plant:
        # guaranteed hits (some later overwritten by other plants on a
        # shared bucket — harmless, both sides see the final table) and
        # guaranteed-expired entries every 8th packet
        for i in range(0, p, 4):
            w = int(rng.integers(0, ways))
            bb = int(b[i])
            valid[bb, w] = 1
            src[bb, w], dst[bb, w] = key[0][i], key[1][i]
            ports[bb, w], proto[bb, w] = key[2][i], key[3][i]
            time[bb, w] = 100 if i % 8 == 0 else 950
    if all_invalid:
        valid[:] = 0
    return (jnp.asarray(b), *(jnp.asarray(k) for k in key),
            jnp.asarray(valid), jnp.asarray(src), jnp.asarray(dst),
            jnp.asarray(ports), jnp.asarray(proto), jnp.asarray(time))


@pytest.mark.parametrize("ways", [1, 2, 4])
def test_sess_probe_matches_reference(ways):
    """Planted hits, planted expired entries (now - time > max_age)
    and random misses across way counts: interpret-mode kernel ==
    gather-rung reference on both outputs."""
    args = _sess_case(ways, seed=17 + ways)
    now, max_age = 1000, 200  # time=100 plants are expired, 950 live
    f_k, w_k = sess_probe_ways(*args, now, max_age, interpret=True)
    f_r, w_r = _probe_ways_reference(*args, now, max_age)
    assert bool(np.asarray(f_k).any())  # plants actually landed
    np.testing.assert_array_equal(np.asarray(f_k), np.asarray(f_r))
    np.testing.assert_array_equal(np.asarray(w_k), np.asarray(w_r))


def test_sess_probe_all_miss_and_no_age_check():
    """All-invalid table: found all-False, way all-0 (the argmax
    convention). The callers' now=0/max_age=_BIG "no age check"
    convention is vacuous for non-negative time ticks."""
    args = _sess_case(4, seed=3, p=33, all_invalid=True)
    f_k, w_k = sess_probe_ways(*args, 1000, 200, interpret=True)
    assert not np.asarray(f_k).any()
    np.testing.assert_array_equal(np.asarray(w_k), 0)

    args = _sess_case(2, seed=9, p=65)
    f_k, w_k = sess_probe_ways(*args, 0, _BIG, interpret=True)
    f_r, w_r = _probe_ways_reference(*args, 0, _BIG)
    np.testing.assert_array_equal(np.asarray(f_k), np.asarray(f_r))
    np.testing.assert_array_equal(np.asarray(w_k), np.asarray(w_r))


def test_session_pallas_fits_budget():
    assert session_pallas_fits(SimpleNamespace(sess_slots=1 << 12))
    limit = SESS_PALLAS_VMEM_BUDGET // 24
    assert session_pallas_fits(SimpleNamespace(sess_slots=limit))
    assert not session_pallas_fits(SimpleNamespace(sess_slots=limit + 1))
    assert not session_pallas_fits(SimpleNamespace(sess_slots=0))
    assert not session_pallas_fits(SimpleNamespace())


# --- LPM: fused per-length binary search ------------------------------


@pytest.mark.parametrize("seed,n_routes,fib_slots",
                         [(3, 40, 64), (7, 200, 256)])
def test_lpm_pallas_matches_oracle(seed, n_routes, fib_slots):
    """Seeded random tables with ECMP groups: the interpret-mode fused
    lookup, the unrolled LPM walk and the NumPy oracle agree on every
    FibResult field (odd packet count exercises the tile padding)."""
    b = _random_table(seed, n_routes, fib_slots, ecmp_groups=4)
    t = b.to_device()
    rng = np.random.default_rng(seed + 2)
    pkts = _probe_traffic(b, rng, 257)
    oracle = NumpyLpmOracle(b).lookup(pkts)
    assert_fib_equal(_fib_lookup_lpm_pallas(t, pkts, interpret=True),
                     oracle)
    assert_fib_equal(fib_lookup_lpm(t, pkts), oracle)


def test_lpm_pallas_edge_tables():
    """Empty table (all-miss), /0-only (all-hit), /32 host routes and
    a duplicate prefix (lowest slot wins the tie): fused == unrolled
    == oracle through the same resolver."""
    from vpp_tpu.pipeline.vector import Disposition

    rng = np.random.default_rng(21)

    def check(b, pkts):
        oracle = NumpyLpmOracle(b).lookup(pkts)
        t = b.to_device()
        assert_fib_equal(
            _fib_lookup_lpm_pallas(t, pkts, interpret=True), oracle)
        assert_fib_equal(fib_lookup_lpm(t, pkts), oracle)

    empty = TableBuilder(_lpm_cfg(fib_slots=16, fib_impl="lpm"))
    empty.add_route("10.0.0.0/8", 1, Disposition.REMOTE, slot=0)
    pkts = _probe_traffic(empty, rng, 65)
    empty.del_route("10.0.0.0/8")
    check(empty, pkts)

    b = TableBuilder(_lpm_cfg(fib_slots=16, fib_impl="lpm"))
    b.add_route("0.0.0.0/0", 1, Disposition.REMOTE, next_hop=9)
    check(b, _probe_traffic(b, rng, 33))

    b = TableBuilder(_lpm_cfg(fib_slots=16, fib_impl="lpm"))
    b.add_route("10.1.1.7/32", 2, Disposition.LOCAL, slot=3)
    b.add_route("10.1.1.8/32", 3, Disposition.LOCAL, slot=1)
    b.add_route("10.1.1.0/24", 4, Disposition.REMOTE, slot=0)
    check(b, _probe_traffic(b, rng, 64))


def test_fib_fused_dispatch_is_lpm_off_tpu():
    assert not use_pallas()
    b = _random_table(13, 60, 64, ecmp_groups=2)
    t = b.to_device()
    pkts = _probe_traffic(b, np.random.default_rng(14), 128)
    r_f = fib_lookup_lpm_fused(t, pkts)
    r_l = fib_lookup_lpm(t, pkts)
    for a, c in zip(jax.tree_util.tree_leaves(r_f),
                    jax.tree_util.tree_leaves(r_l)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


# --- selection ladders and the mesh rejection -------------------------


def test_classifier_ladder_pallas_rung():
    kw = dict(nrules=100, bv_min_rules=8, mxu_threshold=16)
    sel = lambda knob, bv, mxu, pok: select_impl(  # noqa: E731
        knob, bv, mxu, pallas_ok=pok, **kw)
    assert sel("pallas", True, True, True) == "pallas"
    assert sel("pallas", True, True, False) == "bv"   # backend gate
    assert sel("pallas", False, True, True) == "mxu"  # structure gate
    assert sel("pallas", False, False, True) == "dense"
    assert sel("auto", True, True, True) == "pallas"
    assert sel("auto", True, True, False) == "bv"
    assert sel("bv", True, True, True) == "bv"        # explicit knob
    assert select_impl("auto", True, True, 4, 8, 2,
                       pallas_ok=True) == "mxu"       # under bv_min


def test_fib_ladder_pallas_rung():
    assert select_fib_impl("pallas", True, 10, 100, True) == "pallas"
    assert select_fib_impl("pallas", True, 10, 100, False) == "lpm"
    assert select_fib_impl("pallas", False, 10, 100, True) == "dense"
    assert select_fib_impl("auto", True, 200, 100, True) == "pallas"
    assert select_fib_impl("auto", True, 200, 100, False) == "lpm"
    assert select_fib_impl("auto", True, 50, 100, True) == "dense"
    assert select_fib_impl("lpm", True, 10, 100, True) == "lpm"


def test_session_ladder_pallas_rung():
    assert select_session_impl("gather", True) == "gather"
    assert select_session_impl("pallas", True) == "pallas"
    assert select_session_impl("pallas", False) == "gather"
    assert select_session_impl("auto", True) == "pallas"
    assert select_session_impl("auto", False) == "gather"


def _mesh_cfg(**kw):
    base = dict(max_tables=2, max_rules=8, max_global_rules=8,
                max_ifaces=8, fib_slots=16, sess_slots=64,
                nat_mappings=2, nat_backends=4)
    base.update(kw)
    return DataplaneConfig(**base)


@pytest.mark.parametrize("knob", ["classifier", "fib_impl",
                                  "session_impl"])
def test_mesh_rejects_explicit_pallas_knob(knob):
    """An explicit pallas knob on a sharded mesh fails at CONFIG time
    with a message naming PARTITION_RULES (never inside a pallas_call
    trace); rule_shards=1 and auto stay legal."""
    cfg = _mesh_cfg(**{knob: "pallas"})
    with pytest.raises(ValueError, match="PARTITION_RULES"):
        validate_partitioning(cfg, rule_shards=2)
    validate_partitioning(cfg, rule_shards=1)
    validate_partitioning(_mesh_cfg(), rule_shards=2)


def test_config_rejects_unknown_session_impl():
    from vpp_tpu.pipeline.tables import validate_dataplane_config

    with pytest.raises(ValueError, match="session_impl"):
        validate_dataplane_config(_mesh_cfg(session_impl="bogus"))
    for knob in ("gather", "pallas", "auto"):
        validate_dataplane_config(_mesh_cfg(session_impl=knob))


# --- step-level bit-exactness of a fully pallas-knobbed step ----------


def test_pallas_step_bitexact_vs_reference_step():
    """A step composed entirely of pallas rungs equals the bv/lpm/
    gather step leaf-for-leaf on the CPU harness (the dispatch safety
    net at full-pipeline scope: classify verdicts, FIB resolution,
    session state and counters all identical)."""
    from vpp_tpu.pipeline.vector import Disposition

    rng = np.random.default_rng(31)
    b = TableBuilder(_lpm_cfg(fib_slots=64, fib_impl="lpm",
                              classifier="bv"))
    b.set_interface(0, InterfaceType.UPLINK, apply_global=True)
    b.set_global_table(random_rules(rng, 6))
    b.add_route("0.0.0.0/0", 1, Disposition.REMOTE, next_hop=7)
    b.add_route("10.0.0.0/8", 2, Disposition.REMOTE)
    b.add_route("10.1.1.0/24", 3, Disposition.LOCAL)
    t = b.to_device()
    pkts = _probe_traffic(b, rng, 128)
    now = jnp.asarray(7, jnp.int32)

    step_ref = make_pipeline_step("bv", fib_impl="lpm",
                                  sess_impl="gather")
    step_pal = make_pipeline_step("pallas", fib_impl="pallas",
                                  sess_impl="pallas")
    r_ref = step_ref(t, pkts, now)
    r_pal = step_pal(t, pkts, now)
    for a, c in zip(jax.tree_util.tree_leaves(r_pal),
                    jax.tree_util.tree_leaves(r_ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


# --- the PALLAS_KERNELS manifest lint (registry pass, run tier-1) -----


def test_pallas_manifest_lint_clean():
    if str(TOOLS) not in sys.path:
        sys.path.insert(0, str(TOOLS))
    from analysis.registries import partitions_lint

    assert partitions_lint() == []
