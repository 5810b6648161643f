"""Readings that set the check's limits: the program's numbers and the
control's, over several seeds in one process.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 --seconds 3 \
        [--faults all|stale,backend0,...]

Builds the cell's node once (set-up as in a run), then for each seed
serves a short window of the cell's own traffic at its own load, drains
it, and compares the sampled frames twice: as the program served them
(the lower reading), and with the configuration's control in the
program's place: the reference with one stated guarantee broken
(``control`` in the configuration file; the upper reading). Prints one
JSON line per seed. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)
FAULT_SEED_STEP = 7919


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--faults", default="",
                    help="comma-separated faults of benchmark/faults.py "
                         "('all' for every one): serve three more seeds "
                         "with each planted")
    ap.add_argument("--debug-cpu", action="store_true")
    args = ap.parse_args(argv)
    from benchmark import run

    args.trace = 0
    spec, cell, cfg, mix = run.prepare(args)
    run.start_jax(spec, args.debug_cpu)
    devs = run.devices_or_exit(cell, args.debug_cpu)

    from benchmark.check import compare, compare_flows, sample_ids
    from benchmark.gen import Generator

    seeds = [int(s) for s in args.seeds.split(",")]
    path, world, system = run.ready(spec, cfg, mix, seeds[0])
    dp = path.dp
    ref_mod = spec.reference(cfg)
    run.say(f"device {devs[0].device_kind} x{len(devs)}; rungs "
            f"{system.rungs(dp)}; control {cfg['control']}")
    runs = [(s, None) for s in seeds]
    if args.faults:
        from benchmark.faults import PLANTS

        names = list(PLANTS) if args.faults == "all" \
            else args.faults.split(",")
        # fresh seeds for each fault: flows a seed already served have
        # left their sessions behind, which would hide a lost state
        runs += [(s + FAULT_SEED_STEP * (j + 1), p)
                 for j, p in enumerate(names) for s in seeds[:3]]
    for seed, plant in runs:
        undo = PLANTS[plant](dp) if plant else None
        gen = Generator(mix, world, seed)
        due = gen.due_times(args.seconds)
        upto = int(4e7 // gen.frame_pkts) if due is None else len(due)
        load = run.load_for(path, gen, mix, upto)
        t0 = time.perf_counter()
        if due is None:
            load.saturate(t0 + args.seconds)
        else:
            load.paced(due + t0, 0, t0 + args.seconds)
        run.close_window(load, gen, world)
        if undo is not None:
            undo()
        ids = sample_ids(load, gen.key, int(mix["check_frames"]))
        ref = ref_mod.Reference(cfg, world)
        prog = compare(ref, gen, load, ids)
        flows = compare_flows(ref, load)
        cont = compare(ref_mod.Reference(cfg, world), gen, load, ids,
                       control=ref_mod.Reference(cfg, world,
                                                 control=cfg["control"]))
        print(json.dumps({"seed": seed, "fault": plant,
                          "program": dict(prog["numbers"],
                                          **flows["numbers"]),
                          "control": cont["numbers"],
                          "checked_pkts": prog["checked_pkts"],
                          "probe_pkts": flows["probe_pkts"],
                          "kinds": prog["kinds"],
                          "frames": len(load.pushed),
                          "refused": len(load.refused)}), flush=True)
    path.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
