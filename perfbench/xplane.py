"""Reduction of a JAX profiler trace (`.xplane.pb`) to the device numbers.

Two steps, so that the arithmetic can be checked without a chip:

`load(path)` turns the file into plain rows `[plane, line, name, start_ns,
duration_ns]` with `jax.profiler.ProfileData` (parsing needs JAX, not a
device). `reduce(rows)` is plain Python over those rows:

- busy: the union of the intervals in which an operation ran on the device
  (line "XLA Ops" of each `/device:TPU:n` plane), averaged over the device
  planes; the window is the traced span as the launcher's clock has it
  (tracing on -> tracing off), or, where none is given or the device's
  events span more, from the first to the last device event: a span with
  one launch in it is mostly idle, not mostly busy. Idle share = 1 - busy /
  window;
- kernel time by name: the device durations of the launched programs (line
  "XLA Modules"), summed per program name with the `(fingerprint)` cut off,
  so `jit_place_table_chain` keeps its name across compiles;
- `device_ops`: the ten operations that took most device time;
- `idle_gaps`: the idle time between launched programs, summed by the
  program whose launch ended the gap (`before_<name>`), ten longest.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: the placement programs the served path launches
PLACEMENT = ("jit_place_table_chain", "jit_place_table_wave",
             "jit_place_task_group_jit")


def load(path: str) -> List[list]:
    from jax.profiler import ProfileData

    rows = []
    data = ProfileData.from_file(path)
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                name = ev.name if line.name == MODULES_LINE \
                    else op_name(ev.name)
                rows.append([plane.name, line.name, name,
                             int(ev.start_ns), int(ev.duration_ns)])
    return rows


def op_name(name: str) -> str:
    """The profiler names a device operation by its whole HLO line,
    `%while.53 = (s32[], f32[16384,8]{...}, ...) while(...)`: keep what
    stands before the ` = `."""
    return name.split(" = ", 1)[0].lstrip("%")[:64]


def program_name(name: str) -> str:
    """`jit_place_table_chain(1234567890)` -> `jit_place_table_chain`."""
    return name.split("(", 1)[0].strip()


def union_ns(intervals: List[Tuple[int, int]]) -> int:
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def reduce(rows: List[list], traced_s: float = 0.0) -> dict:
    planes = sorted({r[0] for r in rows})
    if not planes:
        return {"busy_s": 0.0, "window_s": float(traced_s), "devices": 0,
                "programs": {}, "device_ops": [], "idle_gaps": []}
    t_lo = min(r[3] for r in rows)
    t_hi = max(r[3] + r[4] for r in rows)
    busy = 0
    ops: Dict[str, int] = {}
    programs: Dict[str, Dict[str, float]] = {}
    gaps: Dict[str, int] = {}
    for p in planes:
        op_rows = [r for r in rows if r[0] == p and r[1] == OPS_LINE]
        mods = sorted((r[3], r[3] + r[4], program_name(r[2])) for r in rows
                      if r[0] == p and r[1] == MODULES_LINE)
        busy += union_ns([(r[3], r[3] + r[4]) for r in op_rows]
                         or [(s, e) for s, e, _n in mods])
        for r in op_rows:
            ops[r[2]] = ops.get(r[2], 0) + r[4]
        end = t_lo
        for s, e, name in mods:
            rec = programs.setdefault(name, {"count": 0, "device_s": 0.0})
            rec["count"] += 1
            rec["device_s"] += (e - s) / 1e9
            if s > end:
                gaps["before_" + name] = gaps.get("before_" + name, 0) \
                    + (s - end)
            end = max(end, e)
    n = len(planes)

    def top(d: Dict[str, int]) -> list:
        return [[k, v / 1e9 / n] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {"busy_s": busy / 1e9 / n,
            "window_s": max(float(traced_s), (t_hi - t_lo) / 1e9),
            "devices": n, "programs": programs,
            "device_ops": top(ops), "idle_gaps": top(gaps)}


def placement_device_s(reduced: dict) -> Tuple[float, int]:
    """Device seconds and launches of the placement programs."""
    s = sum(reduced["programs"].get(n, {}).get("device_s", 0.0)
            for n in PLACEMENT)
    k = sum(int(reduced["programs"].get(n, {}).get("count", 0))
            for n in PLACEMENT)
    return s, k


def newest_trace(trace_dir: str) -> str:
    """The newest `.xplane.pb` under a `jax.profiler.start_trace` dir, or
    "" where there is none."""
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")),
                   key=os.path.getmtime)
    return files[-1] if files else ""


def reduce_dir(trace_dir: str, traced_s: float = 0.0) -> dict:
    path = newest_trace(trace_dir)
    if not path:
        return {"error": f"no .xplane.pb under {trace_dir}"}
    out = reduce(load(path), traced_s)
    out["trace_bytes"] = os.path.getsize(path)
    return out
