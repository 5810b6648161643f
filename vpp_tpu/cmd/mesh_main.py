"""vpp-tpu-mesh-agent: the multi-chip vswitch process.

Boots a MeshRuntime — N cooperating node agents over one
(node, rule) device mesh with the all_to_all ICI fabric as the
inter-node data plane (parallel/runtime.py). This is the deployed
form of the multi-chip data plane: the same binary shape as
vpp-tpu-agent, but one process drives every local chip as a mesh of
vswitch nodes (the JAX process model — one process per host, all
local devices).

Reference analog: N DaemonSet replicas of contiv-agent joined by the
VXLAN full-mesh (plugins/contiv/node_events.go:184-250,
k8s/contiv-vpp.yaml:150) — collapsed into one process whose fabric is
the device interconnect. Config adds a ``mesh`` section:

    mesh:
      nodes: 4          # mesh rows (vswitch nodes)
      rule_shards: 2    # global-ACL rule-axis shards
"""

from __future__ import annotations

import logging
import signal
import threading

log = logging.getLogger("vpp_tpu.mesh_agent")


def main(argv=None) -> int:
    import argparse

    from vpp_tpu.cmd.config import load_config
    from vpp_tpu.compile_cache import enable_compile_cache
    from vpp_tpu.parallel.runtime import MeshRuntime

    parser = argparse.ArgumentParser(prog="vpp-tpu-mesh-agent")
    parser.add_argument("--config", default=None, help="agent YAML config")
    parser.add_argument("--nodes", type=int, default=None,
                        help="mesh rows (overrides mesh.nodes; default: "
                             "all local devices / rule shards)")
    parser.add_argument("--rule-shards", type=int, default=None,
                        help="overrides mesh.rule_shards")
    parser.add_argument("--coordinator", default=None,
                        help="jax.distributed coordinator host:port — "
                             "enables MULTI-HOST mode (one process per "
                             "host; overrides mesh.coordinator)")
    parser.add_argument("--num-processes", type=int, default=None)
    parser.add_argument("--process-id", type=int, default=None)
    args = parser.parse_args(argv)
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    config = load_config(args.config)
    enable_compile_cache()
    rule_shards = (
        args.rule_shards if args.rule_shards is not None
        else config.mesh.rule_shards
    )
    n_nodes = args.nodes if args.nodes is not None else config.mesh.nodes
    coordinator = (args.coordinator if args.coordinator is not None
                   else config.mesh.coordinator)
    if coordinator:
        # multi-host: the SAME binary on every host, one process each;
        # jax.distributed must come up before any backend touch, then
        # n_nodes counts the WHOLE cluster's mesh rows
        from vpp_tpu.parallel.multihost import (
            MultiHostRuntime, init_multihost,
        )

        num_procs = (args.num_processes if args.num_processes is not None
                     else config.mesh.num_processes)
        proc_id = (args.process_id if args.process_id is not None
                   else config.mesh.process_id)
        if num_procs <= 0 or proc_id < 0:
            parser.error("--coordinator requires --num-processes and "
                         "--process-id (or the mesh.* config keys)")
        init_multihost(coordinator, num_procs, proc_id)
    if not n_nodes:
        # after any distributed init: jax.devices() is then the GLOBAL
        # device set, so the default covers the whole fleet's rows
        import jax

        n_nodes = max(1, len(jax.devices()) // rule_shards)
    if coordinator:
        runtime = MultiHostRuntime(n_nodes, config,
                                   rule_shards=rule_shards)
    else:
        runtime = MeshRuntime(n_nodes, config, rule_shards=rule_shards)
    stop = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_: stop.set())
    runtime.start()
    log.info(
        "mesh agent up: %d nodes x %d rule shards, agents %s",
        runtime.n_nodes, rule_shards,
        [a.config.node_name for a in runtime.agents],
    )
    stop.wait()
    log.info("shutting down")
    runtime.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
