"""Open-loop rate sweep of a paced cell's traffic, to find its knee: the
highest offered rate with no growing backlog. The cell then offers a
fixed share of that rate, written into its mix file as ``rate_fps``.

    python3 benchmark/sweep.py --workload <cell> --rates 500,1000,2000 --seconds 5

Builds the node once, then serves each rate for ``--seconds`` and prints
one JSON line per rate: frames offered and refused, latency p50/p99 over
each half of the window (a backlog that grows shows as a second half far
above the first) and the frames still in flight when the window closed.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--debug-cpu", action="store_true")
    args = ap.parse_args(argv)
    from benchmark import run

    spec, cell, cfg, mix = run.prepare(args)
    run.start_jax(spec, args.debug_cpu)
    run.devices_or_exit(cell, args.debug_cpu)

    import numpy as np

    from benchmark.gen import Generator
    from benchmark.served import Feed, Load

    path, world, _ = run.ready(spec, cfg, mix, args.seed)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        gen = Generator(dict(mix, rate_fps=rate), world, args.seed + i)
        due = gen.due_times(args.seconds)
        load = Load(path, Feed(gen), set())
        t0 = time.perf_counter()
        load.paced(due + t0, 0, t0 + args.seconds)
        inflight = len(load.pushed) - len(load.got)
        load.finish(60.0)
        half = len(due) // 2
        lat = [np.array([(load.got[k][0] - due[k] - t0) * 1e6
                         for k in ks if k in load.got])
               for ks in (range(half), range(half, len(due)))]
        q = lambda a, p: float(np.percentile(a, p)) if len(a) else None  # noqa: E731
        print(json.dumps({
            "rate_fps": rate, "pkts_per_s": rate * gen.frame_pkts,
            "offered": len(due), "refused": len(load.refused),
            "inflight_at_close": inflight,
            "p50_us": [q(a, 50) for a in lat], "p99_us": [q(a, 99) for a in lat],
        }), flush=True)
    path.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
