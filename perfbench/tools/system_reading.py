#!/usr/bin/env python3
"""One reading of the program's system path, not a cell: a configuration's
cluster loaded into a `Server`, then system jobs of one kind registered one
after the other, each waited for, and the whole judged by the plain
reference (`check.replay`).

    python3 perfbench/tools/system_reading.py --seed 2147489001 --jobs 20 \
        --config perfbench/configs/baseline-10k.json \
        --kinds perfbench/testdata/system-600/config.json --kind agent-high

`--kinds` names the file whose `kinds` record the kind is taken from, so
that a kind can be read on another configuration's cluster. Per eval: the
wall from register to the terminal eval, placements, evictions, the
compilations and cache loads in it, the milliseconds its server histograms
grew by (`eval.phase.*`, `plan_apply.*`: where the host time went), and the
device time of each program it launched (one profile an eval, `xplane.py`).
Writes <--out>/system_reading.<seed>.json (`perfbench_out/` unless said;
`perfbench/testdata/system-reading/` keeps such summaries); the summary is
the last line of standard output. Without a TPU it exits 3, unless
`--rehearsal` (the CPU, at the configuration's rehearsal size).
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

TERMINAL = ("complete", "failed", "blocked", "cancelled")
#: the server histograms whose growth an eval's row holds, by prefix
PHASES = ("eval.phase.", "plan_apply.")
#: the system verdict's numbers, each with the limit 0; the control
#: (`check._control_system`) is read on them too
SYSTEM_NUMBERS = ("system_missing", "system_extra", "preempt_wrong")


def phase_sums(server) -> dict:
    hists = server.metrics.snapshot().get("histograms") or {}
    return {k: float(h["sum"]) for k, h in hists.items()
            if k.startswith(PHASES)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--kinds", required=True)
    ap.add_argument("--kind", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--jobs", type=int, default=20)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--out", default=os.path.join(ROOT, "perfbench_out"))
    args = ap.parse_args(argv)

    with open(args.config) as f:
        cfg = json.load(f)
    with open(args.kinds) as f:
        record = json.load(f)["kinds"][args.kind]
    cfg = dict(cfg, kinds={args.kind: record}, mix={args.kind: 1})
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        cfg.update(cfg["rehearsal"])

    from nomad_tpu.lib import backend

    backend.setup_compile_cache()
    import jax

    import adapter
    import check
    import cluster as cl
    import launcher
    import reference
    import xplane

    dev = backend.resolve()
    if dev.platform != "tpu" and not args.rehearsal:
        print(f"needs a TPU, JAX reports platform={dev.platform}",
              file=sys.stderr)
        return 3
    watch = launcher.Watch()
    jax.monitoring.register_event_duration_secs_listener(watch.on_duration)

    from nomad_tpu.server import Server, ServerConfig
    from nomad_tpu.structs.codec import to_wire

    out_dir = args.out
    os.makedirs(out_dir, exist_ok=True)
    trace_dir = os.path.join(out_dir, f"system-trace-{os.getpid()}")
    t0 = time.monotonic()
    s = Server(ServerConfig(heartbeat_ttl=3600.0))
    load = launcher.load_cluster(s, cfg, args.seed)
    s.start()
    load["setup_s"] = round(time.monotonic() - t0, 3)
    evals, jobs = [], []
    try:
        for k in range(args.jobs):
            spec = cl.make_job(cfg, args.seed, k, args.kind, 1)
            job = adapter.to_job(spec)
            compiles, phases = watch.compiles, phase_sums(s)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            t = time.monotonic()
            ev = s.job_register(job)
            got = s.wait_for_eval(ev.id, statuses=TERMINAL, timeout=900.0)
            wall = time.monotonic() - t
            jax.profiler.stop_trace()
            red = xplane.reduce_dir(trace_dir, wall)
            shutil.rmtree(trace_dir, ignore_errors=True)
            allocs = [rec for rec in (
                check.alloc_record(to_wire(a))
                for a in s.state.allocs_by_job("default", spec["id"]))
                if rec is not None]
            jobs.append({"spec": spec, "allocs": allocs,
                         "enqueued": ev.modify_index,
                         "finished": got.modify_index if got else None})
            row = {"k": k, "status": got.status if got else None,
                   "wall_s": round(wall, 4), "placements": len(allocs),
                   "evictions": sum(len(a["preempted"]) for a in allocs),
                   "compiles": watch.compiles - compiles,
                   "phase_ms": {name: round(v - phases.get(name, 0.0), 3)
                                for name, v in phase_sums(s).items()
                                if v != phases.get(name, 0.0)},
                   "programs": red.get("programs"),
                   "busy_s": red.get("busy_s")}
            evals.append(row)
            print(json.dumps(row), flush=True)
        memory = max((int((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0)) for d in jax.devices()), default=0)
    finally:
        s.shutdown()
    t = time.monotonic()
    numbers = check.replay(cl.Cluster(cfg, args.seed),
                           check.serial_order(jobs), set(),
                           rnd_control=reference.bf16)
    control = {k: numbers["control"][k] for k in SYSTEM_NUMBERS}
    summary = {
        "config": cfg["name"], "nodes": cfg["nodes"],
        "allocs": cfg["allocs"], "kind": args.kind, "record": record,
        "seed": args.seed, "jobs": args.jobs,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": dev.count, "memory_peak_bytes": memory},
        "load": load, "check_s": round(time.monotonic() - t, 3),
        "numbers": {k: numbers[k] for k in SYSTEM_NUMBERS + (
            "overcommitted_nodes", "system_placed", "system_refused",
            "evictions", "system_interleaved")},
        "control": dict(control, correct=not any(control.values())),
        "worst": numbers["worst"], "fill_at_end": numbers["fill"],
        "wall_s": [e["wall_s"] for e in evals]}
    with open(os.path.join(out_dir, f"system_reading.{args.seed}.json"),
              "w") as f:
        json.dump({"summary": summary, "evals": evals}, f)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
