#!/usr/bin/env python3
"""Several runs of cells in one call, one after the other (one process per
chip at a time), with what the contract asks of a set of runs worked out at
the end: per metric the median and the spread — the distance between the
first and the third quartile (`statistics.quantiles(values, n=4)`) as a
share of the median.

    python3 perfbench/tools/runs.py --tag set1 --seconds 40 \
        baseline-10k.flood:0:11,12,13 baseline-10k.flood:1:14

Each job is workload:trace:seed[,seed...][:flag[,flag]] — flags are passed
to run.py as `--flag`. Results go to chiprun_out/<tag>.jsonl (the run's
record and result, one object per run).
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def spread(values):
    if len(values) < 2:
        return None
    q = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q[2] - q[0]) / med if med else None


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tag", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("jobs", nargs="+")
    args = ap.parse_args()
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{args.tag}.jsonl")
    table = {}
    with open(path, "a") as sink:
        for job in args.jobs:
            parts = job.split(":")
            workload, trace, seeds = parts[0], parts[1], parts[2]
            flags = [f"--{f}" for f in parts[3].split(",")] \
                if len(parts) > 3 else []
            for seed in seeds.split(","):
                cmd = [sys.executable, os.path.join(ROOT, "perfbench",
                                                    "run.py"),
                       "--workload", workload, "--seed", seed,
                       "--seconds", str(args.seconds), "--trace", trace,
                       "--out", out_dir] + flags
                t0 = time.time()
                p = subprocess.run(cmd, cwd=ROOT, stdout=subprocess.PIPE,
                                   text=True)
                wall = time.time() - t0
                lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
                row = {"workload": workload, "trace": int(trace),
                       "seed": int(seed), "flags": flags, "rc": p.returncode,
                       "wall_s": round(wall, 1)}
                try:
                    row["result"] = json.loads(lines[-1])
                    row["record"] = json.loads(lines[-2])
                except (IndexError, ValueError):
                    row["stdout_tail"] = lines[-3:]
                sink.write(json.dumps(row) + "\n")
                sink.flush()
                res = row.get("result") or {}
                key = (workload, int(trace), ",".join(flags))
                for m, v in (res.get("metrics") or {}).items():
                    table.setdefault(key, {}).setdefault(m, []).append(
                        v["value"])
                rec = row.get("record") or {}
                print(f"{workload} trace={trace} seed={seed} {flags} "
                      f"rc={p.returncode} wall={wall:.0f}s correct="
                      f"{res.get('correct')} attempted="
                      f"{res.get('attempted')} failed={res.get('failed')} "
                      f"compiles_in_window="
                      f"{rec.get('compiles_in_window')} checks="
                      + json.dumps({k: v["value"] for k, v in
                                    (res.get("checks") or {}).items()})
                      + " metrics=" + json.dumps(
                          {k: round(v["value"], 4) for k, v in
                           (res.get("metrics") or {}).items()}),
                      flush=True)
    for key, ms in table.items():
        for m, vals in ms.items():
            s = spread(vals)
            print(f"SET {key[0]} trace={key[1]} {key[2]} {m}: n={len(vals)}"
                  f" median={statistics.median(vals):.6g} spread="
                  f"{'n/a' if s is None else format(s, '.4f')} values="
                  + json.dumps([round(v, 4) for v in vals]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
