#!/usr/bin/env python3
"""How `testdata/` was recorded: the first second of a profiler trace as
the plain rows `xplane.reduce` reads, with the name and the number of events
of every line of every plane.

    python3 perfbench/tools/rows.py <trace dir or .xplane.pb> <out.json>
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import xplane  # noqa: E402


def main(src: str, dst: str) -> int:
    from jax.profiler import ProfileData

    path = src if src.endswith(".xplane.pb") else xplane.newest_trace(src)
    rows = xplane.load(path)
    t_lo = min(r[3] for r in rows)
    with open(dst, "w") as f:
        json.dump({"planes_and_lines": [
                       [p.name, ln.name, len(list(ln.events))]
                       for p in ProfileData.from_file(path).planes
                       for ln in p.lines],
                   "rows": [r for r in rows if r[3] - t_lo < 1e9]}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:3]))
