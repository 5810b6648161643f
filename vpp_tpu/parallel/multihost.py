"""Multi-host (DCN) cluster data plane: one mesh across processes.

The reference scales past one machine by running more DaemonSet
replicas joined over the DCN with VXLAN (node_events.go full-mesh).
Here the SAME SPMD cluster step (parallel/cluster.py) runs over a mesh
whose devices span JAX processes — XLA routes the ``all_to_all``
over ICI within a host and DCN between hosts; the program does not
change. What multi-host adds is the *process discipline*:

- ``jax.distributed.initialize`` first (``init_multihost``), so
  ``jax.devices()`` is the global device set.
- Table staging is process-local: each process owns the mesh rows whose
  devices are addressable locally and stages ONLY those nodes'
  builders.
- ``publish()`` and ``step()`` are COLLECTIVE: every process must call
  them the same number of times in the same order (the standard SPMD
  multi-controller contract — the same lockstep the reference gets
  implicitly from per-node processes because VXLAN is connectionless,
  and we get from collectives because the fabric is one program).
  Host-local chunks are assembled into global arrays with
  ``multihost_utils.host_local_array_to_global_array``; results come
  back to each host with the inverse transform.

Tested with real separate processes on the CPU backend
(tests/test_multihost.py: 2 processes x 4 virtual devices); on TPU
pods the same code runs with one process per host
(vpp-tpu-mesh-agent --coordinator ...).
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import multihost_utils
from jax.sharding import PartitionSpec as P

from vpp_tpu.parallel.cluster import (
    ClusterStepResult,
    make_cluster_step,
    mesh_table_specs,
)
from vpp_tpu.parallel.mesh import (
    NODE_AXIS,
    cluster_mesh,
)
from vpp_tpu.parallel.partition import (
    agree_ml,
    bv_mesh_ok,
    select_fib_impl,
    select_impl,
    validate_partitioning,
)
from vpp_tpu.pipeline.dataplane import Dataplane
from vpp_tpu.pipeline.tables import (
    SESSION_FIELDS,
    TELEMETRY_FIELDS,
    FIB_STATE_FIELDS,
    TENANCY_STATE_FIELDS,
    DataplaneConfig,
    DataplaneTables,
    zero_fib_state,
    zero_sessions,
    zero_telemetry,
    zero_tenancy_state,
)
from vpp_tpu.pipeline.vector import PacketVector, make_packet_vector

log = logging.getLogger("vpp_tpu.multihost")


def init_multihost(coordinator_address: str, num_processes: int,
                   process_id: int,
                   heartbeat_timeout_s: int = 100) -> None:
    """``jax.distributed.initialize`` with the runtime's settings; call
    before any other JAX API touches a backend. Raise
    ``heartbeat_timeout_s`` where long jit compiles can starve the
    coordinator heartbeat (the service KILLS tasks that miss it) — on
    toolchains whose initialize() predates the knob (it moved into the
    API mid-0.4.x) the default cadence applies instead."""
    import inspect

    kwargs = dict(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    try:
        params = inspect.signature(jax.distributed.initialize).parameters
    except (TypeError, ValueError):  # C-accelerated callable: assume new
        params = {"heartbeat_timeout_seconds": None}
    if "heartbeat_timeout_seconds" in params:
        kwargs["heartbeat_timeout_seconds"] = heartbeat_timeout_s
    jax.distributed.initialize(**kwargs)


def barrier(name: str) -> None:
    """Cross-process sync point (e.g. 'tables-staged' before a
    collective publish)."""
    multihost_utils.sync_global_devices(name)


class MultiHostCluster:
    """Process-local controller of a cross-process cluster mesh.

    Mirrors ClusterDataplane's surface for the nodes THIS process owns;
    ``publish``/``step`` are collective (see module docstring).
    """

    def __init__(self, n_nodes: int,
                 config: Optional[DataplaneConfig] = None,
                 rule_shards: int = 1):
        self.mesh = cluster_mesh(n_nodes, rule_shards)
        # node configs follow the operator's knobs (ISSUE 12): the
        # partition layer shards BV/ML/session planes, so the fleet
        # runs the same selection ladder as ClusterDataplane
        self.config = config or DataplaneConfig()
        self.n_nodes = n_nodes
        validate_partitioning(self.config, rule_shards)
        # tenancy is not wired into the mesh step (the ClusterDataplane
        # refusal, ISSUE 14): never silently skip an enforcement stage
        if getattr(self.config, "tenancy", "off") != "off":
            raise ValueError(
                "dataplane.tenancy=on is not supported on the mesh "
                "yet: the cluster step would silently skip per-tenant "
                "rate limits and accounting — run tenancy on "
                "standalone dataplanes (docs/TENANCY.md)")
        self._bv_sharded = bv_mesh_ok(self.config, rule_shards)
        if (getattr(self.config, "classifier", "auto") == "bv"
                and rule_shards > 1 and not self._bv_sharded):
            raise ValueError(
                f"classifier=bv on a {rule_shards}-way rule-sharded "
                f"mesh requires max_global_rules "
                f"({self.config.max_global_rules}) divisible by "
                f"{32 * rule_shards} (32·shards)")
        self._ml_sharded = getattr(self.config, "ml_stage", "off") != "off"
        local_ids = {d.id for d in jax.local_devices()}
        self.local_nodes: List[int] = [
            i for i in range(n_nodes)
            if all(d.id in local_ids for d in np.atleast_1d(
                self.mesh.devices[i]).ravel())
        ]
        if not self.local_nodes:
            raise ValueError(
                "no mesh row is fully addressable from this process "
                "(rule_shards must not split a node across hosts)")
        self.nodes: Dict[int, Dataplane] = {}
        for i in self.local_nodes:
            dp = Dataplane(self.config, materialize=False)

            def _no_local_swap():
                raise RuntimeError(
                    "node swap() is collective in multi-host mode: "
                    "stage builders on every process, then call "
                    "MultiHostCluster.publish() on all of them")

            dp._swap_delegate = _no_local_swap
            self.nodes[i] = dp
        self.tables: Optional[DataplaneTables] = None
        self._uplinks = None
        self.epoch = 0
        self._specs = mesh_table_specs(self._bv_sharded,
                                       self._ml_sharded)
        # the config's amortized-aging stride rides every fleet step
        # variant (trace-time static), same as the single-node and
        # ClusterDataplane paths
        from vpp_tpu.pipeline.graph import SWEEP_STRIDE_DEFAULT

        self._sweep_stride = int(
            getattr(self.config, "sess_sweep_stride",
                    SWEEP_STRIDE_DEFAULT))
        # collective steps since the last bulk expire (each cluster
        # step sweeps BOTH pipeline passes) — step calls are collective
        # and the config is fleet-identical, so this counter advances
        # identically on every process
        self._steps_since_expire = 0
        # selection state, agreed COLLECTIVELY at publish() (local
        # eligibility bits allgathered, ladder applied identically on
        # every process — the uplink-guard pattern). Step variants come
        # from the memoized make_cluster_step factory, like
        # ClusterDataplane.
        self._impl = "dense"
        self._use_mxu = False           # legacy view (impl == "mxu")
        self._use_fast = False
        self._ml_mode = "off"
        self._ml_kind = "mlp"
        self._fib_impl = "dense"
        self.mxu_threshold = 512
        self.bv_min_rules = int(
            getattr(self.config, "classifier_bv_min_rules", 1024))
        self.fib_lpm_min_routes = int(
            getattr(self.config, "fib_lpm_min_routes", 256))

    def node(self, i: int) -> Dataplane:
        return self.nodes[i]

    @property
    def fib_impl(self) -> str:
        """The FIB rung the LIVE fleet epoch runs ("dense" | "lpm"),
        agreed across processes at publish — the ClusterDataplane
        ``fib_impl`` twin."""
        return self._fib_impl

    # --- collective operations ---
    def _to_global(self, local_chunk, spec):
        return multihost_utils.host_local_array_to_global_array(
            local_chunk, self.mesh, spec)

    def publish(self) -> int:
        """COLLECTIVE: stack this process's staged node builders and
        assemble the global sharded table epoch (ClusterDataplane.swap
        split across processes). Sessions carry over."""
        # copy under each node's lock: agent threads mutate builders
        # concurrently and a torn row must never reach a global epoch
        # (same contract as ClusterDataplane.swap)
        arrs_by_node = {}
        for i in self.local_nodes:
            with self.nodes[i]._lock:
                arrs_by_node[i] = {
                    k: np.copy(v)
                    for k, v in self.nodes[i].builder.host_arrays().items()
                }
        # ClusterDataplane.swap's misconfiguration guard, made
        # COLLECTIVE: a fabric route to a node without an uplink means
        # inbound traffic lands on reserved interface 0 and is silently
        # dropped. Targets and uplinks live on different processes, so
        # each contributes its local bitmap and every process checks
        # the identical union.
        local_targets = np.zeros(self.n_nodes, np.int32)
        local_uplinked = np.zeros(self.n_nodes, np.int32)
        local_oob = np.zeros(self.n_nodes, np.int32)  # row 2 of gather
        oob_detail = ""
        for i in self.local_nodes:
            arrs = arrs_by_node[i]
            t = arrs["fib_node_id"][arrs["fib_plen"] >= 0]
            t = np.unique(t[t >= 0])
            oob = t[t >= self.n_nodes]
            if len(oob):
                # a raw allocator id where a mesh POSITION belongs.
                # Do NOT raise here: peers are already inside (or
                # entering) the allgather and a one-sided abort would
                # strand them — carry the flag through the gather so
                # EVERY process raises on the same tick.
                local_oob[0] = 1
                oob_detail = (f"node {i} stages routes to node id(s) "
                              f"{oob.tolist()}")
            local_targets[t[t < self.n_nodes]] = 1
            if self.nodes[i].uplink_if is not None:
                local_uplinked[i] = 1
        gathered = np.asarray(multihost_utils.process_allgather(
            np.stack([local_targets, local_uplinked, local_oob])))
        gathered = gathered.reshape(-1, 3, self.n_nodes)
        if gathered[:, 2].max() > 0:
            raise ValueError(
                "staged fabric routes target node id(s) outside this "
                f"{self.n_nodes}-node mesh (allocator id vs mesh "
                f"position aliasing?) {oob_detail}".rstrip())
        targeted = gathered[:, 0].max(axis=0) > 0
        uplinked = gathered[:, 1].max(axis=0) > 0
        bad = np.nonzero(targeted & ~uplinked)[0]
        if len(bad):
            raise ValueError(
                f"fabric routes target node(s) {bad.tolist()} which "
                "have no uplink interface (call add_uplink())")
        local_stack = {}
        for k in DataplaneTables._fields:
            if k in SESSION_FIELDS or k in TELEMETRY_FIELDS \
                    or k in TENANCY_STATE_FIELDS \
                    or k in FIB_STATE_FIELDS:
                continue
            local_stack[k] = np.stack(
                [arrs_by_node[i][k] for i in self.local_nodes])
        host_fields = {
            k: self._to_global(v, getattr(self._specs, k))
            for k, v in local_stack.items()
        }
        if self.tables is not None:
            sess = {f: getattr(self.tables, f) for f in SESSION_FIELDS}
            tel = {f: getattr(self.tables, f) for f in TELEMETRY_FIELDS}
            tnt = {f: getattr(self.tables, f)
                   for f in TENANCY_STATE_FIELDS}
            fib_st = {f: getattr(self.tables, f)
                      for f in FIB_STATE_FIELDS}
        else:
            zero = zero_sessions(self.config,
                                 leading=(len(self.local_nodes),))
            sess = {
                f: self._to_global(np.asarray(zero[f]),
                                   getattr(self._specs, f))
                for f in SESSION_FIELDS
            }
            # telemetry placeholders (ops/telemetry.py): multi-host
            # node configs keep the knob off, so never read
            zt = zero_telemetry(self.config,
                                leading=(len(self.local_nodes),))
            tel = {
                f: self._to_global(np.asarray(zt[f]),
                                   getattr(self._specs, f))
                for f in TELEMETRY_FIELDS
            }
            # tenancy-state placeholders (vpp_tpu/tenancy/): multi-host
            # node configs keep the tenancy knob off too — never read
            ztn = zero_tenancy_state(self.config,
                                     leading=(len(self.local_nodes),))
            tnt = {
                f: self._to_global(np.asarray(ztn[f]),
                                   getattr(self._specs, f))
                for f in TENANCY_STATE_FIELDS
            }
            # per-member ECMP accounting plane (ISSUE 15): replicated
            # along the rule axis, zeros at mesh start
            zf = zero_fib_state(self.config,
                                leading=(len(self.local_nodes),))
            fib_st = {
                f: self._to_global(np.asarray(zf[f]),
                                   getattr(self._specs, f))
                for f in FIB_STATE_FIELDS
            }
        # Classifier/fastpath/ML selection is CLUSTER state: one jitted
        # program serves all nodes, so every choice must be identical
        # fleet-wide — agree like the uplink guard (local eligibility
        # bits, collective min/max, the SAME ladder
        # ClusterDataplane._refresh_selection runs applied to the
        # agreed bits on every process)
        local_mxu_ok = all(
            self.nodes[i].builder.mxu_enabled
            and self.nodes[i].builder.glb_mxu.ok
            for i in self.local_nodes)
        local_bv_ok = all(
            self.nodes[i].builder.bv_ok() for i in self.local_nodes)
        local_nmax = max(
            self.nodes[i].builder.glb_nrules for i in self.local_nodes)
        local_kinds = {int(getattr(self.nodes[i].builder, "ml_kind", 0))
                       for i in self.local_nodes}
        # ml agreement: kinds must be uniform fleet-wide; encode this
        # host's view as (kind, conflict) — min/max detect divergence
        local_kind = local_kinds.pop() if len(local_kinds) == 1 else -1
        local_lpm_ok = all(self.nodes[i].builder.lpm_ok()
                           for i in self.local_nodes)
        local_nroutes = max(self.nodes[i].builder.fib_route_count()
                            for i in self.local_nodes)
        local_lmax = max(int(self.nodes[i].builder.acl_nrules.max())
                         for i in self.local_nodes)
        flags = np.asarray(multihost_utils.process_allgather(
            np.int32([int(local_mxu_ok), int(local_bv_ok),
                      int(local_nmax), local_kind,
                      int(local_lpm_ok),
                      int(local_nroutes),
                      local_lmax]))).reshape(-1, 7)
        mxu_ok = bool(flags[:, 0].min())
        bv_ok = self._bv_sharded and bool(flags[:, 1].min())
        nmax = int(flags[:, 2].max())
        lmax = int(flags[:, 6].max())
        c = self.config
        self._impl = select_impl(
            getattr(c, "classifier", "auto"), bv_ok, mxu_ok, nmax,
            self.bv_min_rules, self.mxu_threshold, local_nrules=lmax)
        self._use_mxu = self._impl == "mxu"
        self._use_fast = bool(getattr(c, "fastpath", True)) and \
            nmax >= int(getattr(c, "fastpath_min_rules", 0))
        self._ml_mode, self._ml_kind = agree_ml(
            getattr(c, "ml_stage", "off"), flags[:, 3])
        # FIB ladder, fleet-agreed like the classifier: lpm only when
        # EVERY process's nodes stage eligible tables (min), at the
        # LARGEST staged route count (max) — the shared rung mapping
        # keeps mesh and standalone selection identical by
        # construction (partition.select_fib_impl; pallas never
        # shards — validate_partitioning)
        self._fib_impl = select_fib_impl(
            getattr(c, "fib_impl", "auto"),
            bool(flags[:, 4].min()), int(flags[:, 5].max()),
            self.fib_lpm_min_routes, pallas_ok=False)
        self.tables = DataplaneTables(**host_fields, **sess, **tel,
                                      **tnt, **fib_st)
        self._uplinks = self._to_global(
            np.array([self.nodes[i].uplink_if or 0
                      for i in self.local_nodes], np.int32),
            P(NODE_AXIS))
        self.epoch += 1
        # per-node api-trace: drain AFTER the guard + assembly succeed,
        # under the cluster epoch (same contract as
        # ClusterDataplane.swap)
        for i in self.local_nodes:
            node = self.nodes[i]
            if node.journal is not None:
                with node._lock:
                    txn = node.builder.drain_recording()
                if txn is not None:
                    node.journal.record(txn, self.epoch)
        return self.epoch

    def make_frames(self, per_local_node_packets: Sequence[list],
                    n: int = 256) -> PacketVector:
        """COLLECTIVE (via array assembly): this process's frames for
        ITS nodes, stacked and lifted to the global [N, P] vector."""
        assert len(per_local_node_packets) == len(self.local_nodes)
        vecs = [make_packet_vector(p, n=n) for p in per_local_node_packets]
        stacked = jax.tree.map(lambda *a: np.stack(a), *vecs)
        return jax.tree.map(
            lambda a: self._to_global(np.asarray(a), P(NODE_AXIS)), stacked)

    def step(self, pkts: PacketVector,
             now: Optional[int] = None) -> ClusterStepResult:
        """COLLECTIVE: one fabric step. ``now`` must be identical on
        every process (pass an explicit logical tick; wall clocks
        drift)."""
        if self.tables is None:
            raise RuntimeError("publish() first")
        if now is None:
            now = self.epoch  # deterministic default, NOT wall clock
        step = self._get_step()
        self._steps_since_expire += 1
        res = step(self.tables, pkts, jnp.int32(now), self._uplinks)
        self.tables = res.tables
        return res

    def _get_step(self, with_payload: bool = False):
        """The jitted cluster step of the fleet-agreed selection (the
        memoized make_cluster_step factory — every process resolves
        the SAME gates from the same collective agreement, so the
        fleet traces identical programs)."""
        return make_cluster_step(
            self.mesh, with_payload=with_payload,
            sweep_stride=self._sweep_stride,
            impl=self._impl, fast=self._use_fast,
            ml_mode=self._ml_mode, ml_kind=self._ml_kind,
            bv_sharded=self._bv_sharded, ml_sharded=self._ml_sharded,
            fib=self._fib_impl)

    def step_wire(self, pkts: PacketVector, payload, now: int):
        """COLLECTIVE: wire-traffic step — headers AND payload bytes
        ride the fabric (ClusterDataplane.step_wire analog; the
        classifier/fastpath/ML gates engage when publish()'s
        fleet-agreed eligibility selected them)."""
        if self.tables is None:
            raise RuntimeError("publish() first")
        step = self._get_step(with_payload=True)
        self._steps_since_expire += 1
        result, deliv_pay = step(
            self.tables, pkts, jnp.asarray(payload), jnp.int32(now),
            self._uplinks)
        self.tables = result.tables
        return result, deliv_pay

    def expire_sessions(self, now: int,
                        max_age: Optional[int] = None,
                        lazy: bool = False) -> None:
        """COLLECTIVE: bulk-age the global session tables (reflective +
        NAT) — the ClusterDataplane.expire_sessions analog. Steady-state
        aging happens INSIDE the fused cluster step (the amortized
        session sweep, ops/session.py); this bulk pass serves idle
        epochs and explicit reclamation. ``now`` must be the
        fleet-agreed tick.

        ``lazy=True`` skips the bulk device pass only when the in-step
        sweep has covered the whole table since the last call (steps x
        2 strides >= buckets — each cluster step sweeps both pipeline
        passes). The decision derives from the collective step counter
        and the fleet-identical config, so every process skips or runs
        the collective identically."""
        from vpp_tpu.ops.session import session_expire

        if self.tables is None:
            return
        if max_age is None:
            max_age = self.config.sess_max_age
        # lazy is sound only for the CONFIGURED timeout (the in-step
        # sweep enforces tables.sess_max_age); the equality check is
        # fleet-deterministic like the rest of the decision
        if lazy and max_age == self.config.sess_max_age:
            steps = self._steps_since_expire
            self._steps_since_expire = 0
            from vpp_tpu.ops.session import sweep_covered

            # node-stacked [N, n_buckets, W]; each cluster step sweeps
            # BOTH pipeline passes
            if sweep_covered(steps, self._sweep_stride, self.tables,
                             bucket_axis=1, passes=2):
                return
        self.tables = session_expire(self.tables, now, max_age)

    # --- host-local views of a step result ---
    def local_rows(self, arr) -> np.ndarray:
        """This process's node rows of a node-stacked global output."""
        loc = multihost_utils.global_array_to_host_local_array(
            arr, self.mesh, P(NODE_AXIS))
        return np.asarray(loc)


class LockstepDriver:
    """Kvstore-coordinated epoch commits for a MultiHostCluster.

    publish() is collective, but config changes originate on ONE host
    (a policy event, a CNI Add). The protocol, per tick of the driver
    loop every process runs:

      1. the requesting process stages its builder mutations locally
         (cross-host state rides the shared kvstore as usual — KSR,
         node events) and bumps the ``commit_req`` counter (CAS);
      2. every process reads the counter LOCALLY (no collective), then
         the fleet agrees on ``min(process_allgather(seen))`` — a tiny
         device collective, so the DECISION to publish is itself
         deterministic and collective;
      3. once every process has seen request N > applied, they all
         publish() on the SAME tick, then step().

    A process that hasn't noticed the request yet holds the whole
    fleet's epoch back (min-agreement) but never deadlocks it — the
    fabric keeps stepping on the old epoch until agreement lands.
    Reference analog: renderer resync events fanning out of one ETCD
    write to every vswitch (plugins/policy watch path); the collective
    min replaces "eventually each node applies" with "all nodes apply
    the same tick".
    """

    def __init__(self, cluster: MultiHostCluster, store,
                 prefix: str = "/mesh/epoch/",
                 expire_every: int = 512):
        self.cluster = cluster
        self.store = store
        self.req_key = prefix + "commit_req"
        self.stop_key = prefix + "stop_req"
        self.applied = 0
        self.ticks = 0
        # stop requests are counted RELATIVE to construction: a stop
        # agreed by a PREVIOUS deployment persists in the store and
        # must not halt a restarted fleet on its first tick. The
        # baseline itself is AGREED (max over an allgather of each
        # process's read) — divergent local reads racing an old
        # fleet's final bump would otherwise stop one process and
        # strand the rest in their next collective. Construction is
        # therefore collective; every process builds its driver at the
        # same point in startup.
        self._stop_base = int(np.asarray(multihost_utils.process_allgather(
            np.int32(int(self.store.get(self.stop_key) or 0)))).max())
        # session aging cadence (in ticks): deterministic from the
        # shared tick count, so the collective expire runs on the same
        # tick fleet-wide
        self.expire_every = expire_every

    def _bump(self, key: str) -> int:
        while True:
            cur = self.store.get(key)
            nxt = int(cur or 0) + 1
            if self.store.compare_and_put(key, cur, nxt):
                return nxt

    def request_commit(self) -> int:
        """Bump the commit counter (any process; CAS-safe)."""
        return self._bump(self.req_key)

    def request_stop(self) -> int:
        """Ask the WHOLE fleet to stop ticking: collectives can't be
        abandoned unilaterally (a peer blocked in one would hang), so
        shutdown is agreed the same way commits are."""
        return self._bump(self.stop_key)

    def tick(self, per_local_node_packets: Sequence[list],
             n: int = 256) -> Optional[ClusterStepResult]:
        """COLLECTIVE: agree on pending commits/stop, publish if the
        whole fleet has seen a commit, then run one fabric step.
        Returns None once the fleet has agreed to stop — no further
        collectives may be issued after that."""
        out = self.tick_fabric(
            lambda t: self.cluster.step(
                self.cluster.make_frames(per_local_node_packets, n=n),
                now=t),
            has_work=True)  # header-mode callers pass explicit frames
        return None if out is self._STOPPED else out

    _STOPPED = object()

    def tick_fabric(self, fabric_fn, has_work: bool = True):
        """COLLECTIVE tick with a caller-supplied fabric step (the wire
        pump's ring->device->ring dispatch). Same agreement protocol as
        tick(); returns ``LockstepDriver._STOPPED`` once the fleet
        agreed to stop, else ``fabric_fn(tick)``'s result (None when
        the step was skipped). fabric_fn MUST issue the identical
        collective sequence on every process.

        ``has_work``: this host's local signal (pending frames). The
        allgather carries it, and when the WHOLE fleet is idle every
        process skips the fabric step on the same tick — an idle
        deployment burns one tiny allgather per tick instead of a full
        device step."""
        seen = np.int32([int(self.store.get(self.req_key) or 0),
                         int(self.store.get(self.stop_key) or 0),
                         int(bool(has_work))])
        gathered = np.asarray(
            multihost_utils.process_allgather(seen)).reshape(-1, 3)
        agreed_req = int(gathered[:, 0].min())
        agreed_stop = int(gathered[:, 1].min())
        fleet_has_work = bool(gathered[:, 2].max())
        if agreed_stop > self._stop_base:
            return self._STOPPED
        pending_commit = agreed_req > self.applied
        if pending_commit:
            self.cluster.publish()
            self.applied = agreed_req
        self.ticks += 1
        out = None
        # a commit tick always steps: in-flight state (sessions) must
        # advance onto the new epoch deterministically everywhere
        if fleet_has_work or pending_commit:
            out = fabric_fn(self.ticks)
        if self.expire_every and self.ticks % self.expire_every == 0:
            # lazy: the bulk collective is skipped only when the
            # in-step amortized sweep has actually covered the whole
            # ring since the last expire (coverage math inside
            # expire_sessions — NOT a mere "did we step" flag, which
            # would skip forever on a busy fleet sweeping a big table
            # far slower than the expire cadence). The decision derives
            # from the collective step counter + fleet-identical
            # config, so no process can diverge on whether this
            # collective happens.
            self.cluster.expire_sessions(now=self.ticks, lazy=True)
        return out


class _LocalWireView:
    """Cluster-shaped LOCAL view for ClusterPump in multi-host mode.

    The pump stages/reads only THIS host's mesh rows; ``step_wire``
    lifts the local staging to global arrays, runs the COLLECTIVE wire
    step, and hands back host-local rows so the pump's writer never
    touches non-addressable shards. ``now`` is set per tick by the
    runtime (the fleet-agreed tick, not wall clock)."""

    def __init__(self, mh: MultiHostCluster):
        self.mh = mh
        self.now = 0

    @property
    def n_nodes(self) -> int:
        return len(self.mh.local_nodes)

    @property
    def epoch(self) -> int:
        return self.mh.epoch

    def step_wire(self, pkts: PacketVector, payload, now=None):
        import types

        mh = self.mh
        g_pkts = jax.tree.map(
            lambda a: mh._to_global(np.asarray(a), P(NODE_AXIS)), pkts)
        g_pay = mh._to_global(np.ascontiguousarray(payload), P(NODE_AXIS))
        res, dpay = mh.step_wire(
            g_pkts, g_pay, now=self.now if now is None else now)

        def localize(tree):
            return jax.tree.map(mh.local_rows, tree)

        return (types.SimpleNamespace(local=localize(res.local),
                                      delivered=localize(res.delivered),
                                      stats=localize(res.stats),
                                      fastpath_pass1=mh.local_rows(
                                          res.fastpath_pass1)),
                mh.local_rows(dpay))


class MultiHostRuntime:
    """The DEPLOYABLE multi-host mesh: real ContivAgents per local
    node over a cross-process MultiHostCluster.

    One MultiHostRuntime per host (vpp-tpu-mesh-agent
    --coordinator ...): each boots agents for the mesh rows its
    devices own, the agents' unchanged renderer/CNI/service/node-event
    commit paths STAGE into their node builders, and every commit is
    routed through LockstepDriver.request_commit — the swap-delegate
    analog of MeshRuntime, except the publish happens on the next
    agreed tick instead of inline (the same eventual-apply the
    reference gets from ETCD watch fan-out). A tick thread steps the
    fabric at a fixed cadence; collectives self-synchronize, so the
    fleet runs at the slowest host's pace.

    Cross-process peer resolution rides the shared kvstore: each agent
    publishes (allocator node id -> mesh position) and the resolver
    reads peers' entries, so node events on ANY host produce fabric
    routes toward the right mesh row.
    """

    POS_PREFIX = "/mesh/pos/"

    def __init__(self, n_nodes: int, base_config, rule_shards: int = 1,
                 store=None, tick_interval: float = 0.02,
                 frame_n: int = 256,
                 on_result: Optional[Callable] = None):
        from vpp_tpu.cmd.agent import ContivAgent
        from vpp_tpu.kvstore.client import connect_store
        from vpp_tpu.parallel.runtime import _node_config

        if store is None:
            if not base_config.store_url:
                raise ValueError(
                    "multi-host mesh requires store_url (a kvstore "
                    "shared by every host)")
            store = connect_store(base_config.store_url,
                                  persist_path=base_config.persist_path)
        self.store = store
        self.cluster = MultiHostCluster(
            n_nodes, base_config.dataplane, rule_shards)
        self.n_nodes = n_nodes
        self.driver = LockstepDriver(self.cluster, store)
        self.tick_interval = tick_interval
        self.frame_n = frame_n
        self.on_result = on_result
        self.last_result: Optional[ClusterStepResult] = None
        for i in self.cluster.local_nodes:
            self.cluster.node(i)._swap_delegate = \
                self.driver.request_commit

        def resolver(nid: int) -> int:
            v = self.store.get(self.POS_PREFIX + str(int(nid)))
            return -1 if v is None else int(v)

        self.agents = []
        for i in self.cluster.local_nodes:
            cfg = _node_config(base_config, i)
            agent = ContivAgent(cfg, store=store,
                                dataplane=self.cluster.node(i),
                                mesh_node_resolver=resolver)
            agent._external_io = True  # no per-agent pump on node handles
            agent.mesh_runtime = self  # `show mesh` on any node's CLI
            self.store.put(self.POS_PREFIX + str(agent.node_id), i)
            self.agents.append(agent)
        self._frames_lock = threading.Lock()
        self._pending: Dict[int, list] = {
            i: [] for i in self.cluster.local_nodes}
        self._tick_thread: Optional[threading.Thread] = None
        # packet IO (io.enabled): per-LOCAL-node ring pairs + ONE
        # tick-driven ClusterPump over the local wire view — the same
        # ring/daemon contract as MeshRuntime, but the fabric step is
        # issued by the tick loop so it interleaves deterministically
        # with the driver's other collectives on every host
        self.ring_pairs = None
        self.cluster_pump = None
        if base_config.io.enabled:
            from vpp_tpu.io.cluster_pump import ClusterPump
            from vpp_tpu.io.rings import IORingPair

            io = base_config.io
            self.ring_pairs = [
                IORingPair(
                    n_slots=io.n_slots, snap=io.snap,
                    shm_name=(f"{io.shm_name}.{i}" if io.shm_name
                              else None),
                    create=True,
                )
                for i in self.cluster.local_nodes
            ]
            self.wire_view = _LocalWireView(self.cluster)
            self.cluster_pump = ClusterPump(self.wire_view,
                                            self.ring_pairs)
            self.cluster_pump.step_when_idle = True
            self.cluster_pump.raise_on_error = True
            # fleet-agreed coalesce bucket: every host stages the SAME
            # global shape every tick (see ClusterPump.max_frames_per_ring)
            self.cluster_pump.max_frames_per_ring = 1
            for agent in self.agents:
                agent.io_pump = self.cluster_pump
            # one designated exporter (MeshRuntime parity): every agent
            # exporting the SHARED pump would overcount by n_local
            self.agents[0].stats.set_pump(self.cluster_pump)

    # --- traffic injection (tests / local IO front-ends) ---
    def inject(self, node: int, packets: Sequence[dict]) -> None:
        if self.cluster_pump is not None:
            # the io tick loop steps the WIRE pump, not _pending —
            # silently queueing here would blackhole forever
            raise RuntimeError(
                "inject() is for header-only mode; with io.enabled "
                "push wire frames into ring_pairs[i].rx instead")
        with self._frames_lock:
            self._pending[node].extend(packets)

    def _drain(self) -> List[list]:
        with self._frames_lock:
            out = [self._pending[i][:self.frame_n]
                   for i in self.cluster.local_nodes]
            for i in self.cluster.local_nodes:
                del self._pending[i][:self.frame_n]
            return out

    # --- lifecycle ---
    def start(self) -> "MultiHostRuntime":
        for agent in self.agents:
            agent.start()
        if self.cluster_pump is not None:
            # the wire step needs live tables and both coalesce-bucket
            # compiles BEFORE traffic; both are collectives, so every
            # host runs them here, in the same order, pre-tick-loop
            self.cluster.publish()
            self.cluster_pump.warm()
            self.cluster_pump.start(dispatch=False)  # writer only
        self._tick_thread = threading.Thread(
            target=self._loop, daemon=True, name="mh-tick")
        self._tick_thread.start()
        return self

    def _loop(self) -> None:
        stopped = LockstepDriver._STOPPED
        while True:
            try:
                if self.cluster_pump is not None:
                    def fabric(tick):
                        self.wire_view.now = tick
                        self.cluster_pump._dispatch_once()
                        return True

                    res = self.driver.tick_fabric(
                        fabric, has_work=self.cluster_pump.has_pending())
                    if res is stopped:
                        return
                else:
                    res = self.driver.tick(self._drain(), n=self.frame_n)
                    if res is None:
                        return  # fleet agreed to stop
                    self.last_result = res
                    if self.on_result is not None:
                        self.on_result(res)
            except Exception:
                # a failed collective leaves the fleet out of step —
                # there is no local recovery; surface it, and
                # best-effort ask peers to stop (helps any that have
                # not yet entered this tick's collectives; ones already
                # inside are unblocked by the coordination service's
                # own timeout)
                log.exception("mesh tick failed; fabric halted")
                try:
                    self.driver.request_stop()
                except Exception:  # noqa: BLE001 — store may be gone too
                    pass
                return
            time.sleep(self.tick_interval)

    def close(self, join_timeout: float = 60.0) -> None:
        if self._tick_thread is not None:
            self.driver.request_stop()
            self._tick_thread.join(timeout=join_timeout)
            if self._tick_thread.is_alive():
                # a dead peer strands our tick thread inside a
                # collective; nothing safe to do but report (process
                # exit reclaims it)
                log.error("tick thread did not stop (peer host down?)")
        pump_stopped = True
        if self.cluster_pump is not None:
            pump_stopped = self.cluster_pump.stop(join_timeout=30.0)
            # in multi-host io mode the TICK thread is the pump's
            # dispatcher: if it is still wedged in a collective (peer
            # down) it can resume into the rings later — freeing them
            # now would be a use-after-free into shared memory
            pump_stopped = pump_stopped and not (
                self._tick_thread is not None
                and self._tick_thread.is_alive())
        for agent in reversed(self.agents):
            agent.close()
        if self.ring_pairs is not None:
            if pump_stopped:
                for rings in self.ring_pairs:
                    rings.close(
                        unlink=bool(self.agents[0].config.io.shm_name))
            else:
                # a wedged writer still holds ring pointers (same
                # policy as MeshRuntime/agent close)
                log.error("cluster pump did not stop; leaving rings "
                          "mapped")
