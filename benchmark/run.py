"""Run one benchmark cell on the served packet path.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Reads ``BENCHMARK.json`` at the repository root and finds the cell's
configuration, traffic mix and metric readers by name (benchmark/spec.py).
Set-up builds the node from the configuration, warms the pump's dispatch
rungs, fills the session table where the mix asks for it and serves
``warm_s`` seconds of the mix; then the window measures for ``--seconds``.
With ``--trace 1`` a profiler trace of a slice of the window gives the
per-layer metrics instead of the end-to-end ones.

The last line of standard output is the result, as JSON. Without a TPU
(or with fewer chips than the cell asks for) the run exits non-zero and
prints no result. ``--debug-cpu`` is a rehearsal at the configuration's
``debug`` sizes on the CPU; its result names the cpu platform.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
# run as a script, sys.path[0] is benchmark/: import from the root instead
sys.path[0] = str(ROOT)


def process_age_s() -> float:
    """Seconds since this process started (set-up counts from here)."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    return (time.clock_gettime(time.CLOCK_BOOTTIME)
            - start_ticks / os.sysconf("SC_CLK_TCK"))


def merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out.get(k) or {}, v) if isinstance(v, dict) else v
    return out


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--debug-cpu", action="store_true",
                    help="rehearse on the CPU at the configuration's "
                         "debug sizes (never a measurement)")
    return ap.parse_args(argv)


def prepare(args, root: Path = ROOT):
    """Cell, configuration (with debug sizes applied) and mix."""
    from benchmark.spec import Spec

    spec = Spec(root)
    cell = spec.cell(args.workload)
    cfg = spec.config(cell["config"])
    mix = spec.traffic(cell["traffic"])
    if args.debug_cpu:
        cfg = merge(cfg, cfg.get("debug", {}))
        mix = merge(mix, mix.get("debug", {}))
    return spec, cell, cfg, mix


def start_jax(spec, debug_cpu: bool) -> None:
    """Point JAX at the CPU for a rehearsal, or at the persistent
    compilation cache inside the checkout (a fixed path, so a second run
    finds every program there)."""
    if debug_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        return
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(spec.root / ".jax_cache")
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def devices_or_exit(cell: dict, debug_cpu: bool):
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu" and not debug_cpu:
        say(f"benchmark: JAX found platform {platform!r} ({len(devs)} "
            f"device(s)); the cell needs a TPU")
        sys.exit(2)
    if len(devs) < int(cell["chips"]) and not debug_cpu:
        say(f"benchmark: the cell asks for {cell['chips']} chips, JAX "
            f"found {len(devs)}")
        sys.exit(2)
    return devs


def memory_peak(devs) -> int:
    peak = 0
    for d in devs:
        try:
            peak = max(peak, int((d.memory_stats() or {})
                                 .get("peak_bytes_in_use", 0)))
        except Exception:  # noqa: BLE001 — backends without stats read 0
            pass
    return peak


def ready(spec, cfg, mix, seed: int, fault=None):
    """The set-up every serving entry shares (a run, control.py,
    sweep.py): build the node, plant ``fault``, warm the pump's dispatch
    rungs, fill the session table where the mix asks for it (flows drawn
    from ``seed``) and start the pump. -> (path, world, system)."""
    from benchmark.gen import Generator
    from benchmark.served import ServedPath

    system = spec.system(cfg)
    t = time.perf_counter()
    dp, world = system.build(cfg)
    say(f"node built and staged in {time.perf_counter() - t:.3f} s "
        f"(process age {process_age_s():.3f} s)")
    if fault is not None:
        fault(dp)
    path = ServedPath(dp, system.io_config(cfg), world)
    t = time.perf_counter()
    path.warm()
    say(f"pump rungs {path.pump.bucket_sizes()} chain_k "
        f"{path.pump.chain_k} warmed in {time.perf_counter() - t:.3f} s; "
        f"kernel rungs {system.rungs(dp)}")
    fill = int(float(mix.get("fill_sessions", 0))
               * int(cfg["dataplane"].get("sess_slots", 4096)))
    if fill:
        t = time.perf_counter()
        n = path.fill_sessions(Generator(mix, world, seed), fill)
        say(f"session fill: {n} new flows in {time.perf_counter() - t:.3f} s")
    path.start()
    return path, world, system


def load_for(path, gen, mix, upto: int, annotate: bool = False):
    """The push/drain loop of one seed's traffic, keeping what the
    check samples and the window's last frames."""
    from benchmark.served import Feed, Load, keep_set

    return Load(path, Feed(gen), keep_set(gen.key, int(mix["check_every"]),
                                          upto),
                annotate=annotate, tail=int(mix.get("tail_frames", 0)))


def close_window(load, gen, world, timeout_s: float = 60.0) -> None:
    """Wait for every pushed frame, then serve the flow probe."""
    from benchmark.check import flow_probe

    load.finish(timeout_s)
    flow_probe(load, gen, world)


def serve(args, spec, cell, cfg, mix, devs, fault=None):
    """Set-up, the window and the drain. Returns the raw run record and
    what the check needs, with the program's state already freed."""
    import numpy as np

    from benchmark.gen import Generator
    from benchmark.served import TraceSlice
    from vpp_tpu.pipeline.dataplane import (
        device_transfer_totals,
        jit_compile_totals,
    )

    path, world, system = ready(spec, cfg, mix, args.seed, fault=fault)
    dp = path.dp
    rungs = system.rungs(dp)
    shapes = system.staged_shapes(dp)
    gen = Generator(mix, world, args.seed)
    fp = gen.frame_pkts
    warm_s = float(mix.get("warm_s", 1.0))
    due = gen.due_times(warm_s + args.seconds)
    upto = int(4e7 // fp) if due is None else len(due)
    load = load_for(path, gen, mix, upto, annotate=bool(args.trace))
    t_w = time.perf_counter()
    t0 = t_w + warm_s
    if due is None:
        load.saturate(t0)
    else:
        due = due + t_w
        n_warm = int(np.searchsorted(due, t0))
        load.paced(due[:n_warm], 0, t0)
    t0 = time.perf_counter()
    k_win = load.next_k
    setup_s = process_age_s()
    stats0 = dict(path.pump.stats)
    xfer0 = sum(device_transfer_totals().values())
    jit0 = sum(jit_compile_totals().values())
    t1 = t0 + args.seconds
    tracer = None
    logdir = None
    if args.trace:
        logdir = tempfile.mkdtemp(prefix="bench-trace-")
        tracer = TraceSlice(logdir, t0 + 0.3 * args.seconds,
                            min(2.0, 0.4 * args.seconds))
        tracer.start()
    if due is None:
        load.saturate(t1)
    else:
        load.paced(due[n_warm:], n_warm, t1)
    t1 = time.perf_counter()
    stats1 = dict(path.pump.stats)
    xfer1 = sum(device_transfer_totals().values())
    jit1 = sum(jit_compile_totals().values())
    if tracer is not None:
        tracer.join(120)
    close_window(load, gen, world)
    path.stop()
    peak = memory_peak(devs)
    win = [k for k in load.pushed if k >= k_win]
    delivered = sum(n for k, (td, n) in load.got.items() if t0 <= td <= t1)
    lat_us = None
    if due is not None:
        lat_us = np.array([(load.got[k][0] - due[k]) * 1e6
                           for k in range(n_warm, load.next_k)
                           if k in load.got])
    refused = [k for k in load.refused if k >= k_win]
    lost_win = sum(load.pushed[k][1] for k in win if k not in load.got)
    record = {
        "seconds": t1 - t0, "setup_s": setup_s,
        "delivered_pkts": delivered, "lat_us": lat_us,
        "stats0": stats0, "stats1": stats1, "xfer_bytes": xfer1 - xfer0,
        "jit_in_window": jit1 - jit0, "shapes": shapes, "rungs": rungs,
        "attempted": sum(load.pushed[k][1] for k in win) + fp * len(refused),
        "failed": lost_win + fp * len(refused),
        "memory_peak_bytes": peak,
        "push_late_p99_ms": _late_p99(load, due, n_warm if due is not None
                                      else 0),
        "frames_window": len(win), "refused_frames": len(refused),
    }
    trace = None
    if tracer is not None:
        from benchmark.tracereduce import load_dir

        if tracer.error is not None:
            say(f"trace failed: {tracer.error!r}")
        else:
            trace = load_dir(logdir)
        shutil.rmtree(logdir, ignore_errors=True)
    record["trace"] = trace
    path.close()
    # free the program's state before the reference runs
    load.path = None
    del path, dp
    gc.collect()
    return record, gen, world, load


def _late_p99(load, due, n0) -> float:
    """How late the generator pushed (open loop only), p99 in ms."""
    import numpy as np

    if due is None:
        return 0.0
    late = [load.pushed[k][0] - due[k] for k in range(n0, load.next_k)
            if k in load.pushed]
    return float(np.percentile(late, 99) * 1e3) if late else 0.0


def check(spec, cfg, mix, gen, world, load):
    """-> (correct, numbers) of the comparison with the reference."""
    from benchmark.check import compare, compare_flows, sample_ids, verdict

    ref = spec.reference(cfg).Reference(cfg, world)
    ids = sample_ids(load, gen.key, int(mix["check_frames"]))
    t = time.perf_counter()
    res = compare(ref, gen, load, ids)
    flows = compare_flows(ref, load)
    say(f"check: {res['checked_pkts']} packets in {res['checked_frames']} "
        f"sampled frames, destinations {res['kinds']}, "
        f"{time.perf_counter() - t:.3f} s; stray frames "
        f"{res['stray_frames']}; columns that differ {res['columns']}; "
        f"flow probe {flows['probe_pkts']} packets, columns that differ "
        f"{flows['columns']}")
    numbers = dict(res["numbers"], **flows["numbers"])
    return verdict(numbers), numbers


def result(args, spec, cell, record, devs):
    """Metric values from the readers this run's kind asks for."""
    from benchmark.spec import peaks
    from benchmark.tracereduce import device_busy, idle_gaps, top_ops

    d0 = devs[0]
    run = dict(record)
    run["busy"] = (device_busy(record["trace"]) if record["trace"]
                   else None)
    run["peaks"] = (peaks(d0.device_kind, spec.root)
                    if d0.platform == "tpu" else None)
    metrics = {}
    for entry, reader in spec.metrics(cell["name"], bool(args.trace)):
        v = reader.read(run)
        if v is not None:
            metrics[entry["name"]] = {"value": float(v), "unit": entry["unit"]}
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs),
              "memory_peak_bytes": record["memory_peak_bytes"]}
    out = {"metrics": metrics, "device": device}
    if args.trace and run["busy"]:
        device["busy_s"] = run["busy"]["busy_s"]
        device["window_s"] = run["busy"]["window_s"]
        out["breakdown"] = {"device_ops": top_ops(record["trace"]),
                            "idle_gaps": idle_gaps(record["trace"])}
    return out


def main(argv=None, fault=None) -> int:
    import numpy as np

    args = parse_args(argv)
    spec, cell, cfg, mix = prepare(args)
    start_jax(spec, args.debug_cpu)
    devs = devices_or_exit(cell, args.debug_cpu)
    say(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
        f"cell {cell['name']} seed {args.seed} seconds {args.seconds} "
        f"trace {args.trace}")
    record, gen, world, load = serve(args, spec, cell, cfg, mix, devs,
                                     fault=fault)
    correct, numbers = check(spec, cfg, mix, gen, world, load)
    out = result(args, spec, cell, record, devs)
    st0, st1 = record["stats0"], record["stats1"]
    lat = record["lat_us"]
    say(f"window: {record['seconds']:.3f} s, {record['frames_window']} "
        f"frames pushed, {record['refused_frames']} refused, "
        f"{record['delivered_pkts']} packets delivered, "
        f"{st1['batches'] - st0['batches']} dispatches, "
        f"{st1['chain_batches'] - st0['chain_batches']} chained, "
        f"compiles in window {record['jit_in_window']}, "
        f"lat samples {0 if lat is None else len(lat)}"
        f"{'' if lat is None or not len(lat) else f' (p95 {np.percentile(lat, 95):.1f} us, p99 {np.percentile(lat, 99):.1f} us)'}, "
        f"generator late p99 {record['push_late_p99_ms']:.3f} ms, "
        f"setup {record['setup_s']:.3f} s")
    drops = {k: st1[k] - st0[k] for k in st1 if k.startswith("drops_")}
    say(f"pump drops in window: {drops}")
    stages = {k: round(st1[k] - st0[k], 6) for k in
              ("t_pack", "t_dispatch", "t_fetch_wait", "t_fetch", "t_write",
               "frames", "pkts", "batches", "icmp_errors") if k in st1}
    say(f"pump stages in window: {stages}")
    from benchmark.check import LIMITS

    for name, v in numbers.items():
        say(f"check {name} {v} limit {LIMITS[name]}")
    line = {"correct": bool(correct), "attempted": int(record["attempted"]),
            "failed": int(record["failed"])}
    line.update(out)
    line["checks"] = {k: {"value": v, "limit": LIMITS[k]}
                      for k, v in numbers.items()}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
