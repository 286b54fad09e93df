"""The one general traffic generator: a mix is a data file of parameters.

`traffic/<name>.json` holds
  loop         "closed" (a fixed number of jobs outstanding; the next is sent
               when a completion is pushed) or "open" (arrivals on a
               schedule, whatever the system does)
  outstanding  closed loop: jobs in flight
  window       "running" (the default: the window opens and closes on the
               running traffic, and work answered inside it counts) or, for
               a closed loop whose jobs are answered in a few lumps a
               window, "drained": the warm-up's jobs are waited for, the
               loop starts anew at the opening, sends for `--seconds`, and
               the clock is read once all that was sent is answered: whole
               jobs only, all of that work over all of that time
  rate_per_s   open loop: offered rate, fixed in the file, never searched
  count        allocations each job asks for
  senders      threads that PUT (few: one process, few threads)
  warmup       bursts   sizes (and optionally kinds) sent all at once: a
                        burst leaves the broker in one drain (the hold
                        window keeps it open), so each reaches one
                        program-axis bucket of the chain kernel, or, with
                        pinned kinds only, one [lanes, lane length] bucket
                        of the wave kernel
               quiet_s  the cell's own traffic then runs until this long
                        has passed with no compilation or cache load
               max_s    and the window opens at the latest after this
  timeout_s    a request not answered this long after the window closed
               has failed
  trace_span_s seconds in the middle of the window that a `--trace 1` run
               traces (4 where the file names none): long enough to hold
               several launches of the cell's placement program

Open-loop arrivals are Poisson in shape and the same for every seed: the
gaps of a block are the mid-point quantiles of the exponential distribution,
scaled so that a block lasts exactly block / rate seconds, in an order
shuffled from the seed. Every seed therefore offers the same set of gaps,
and the same number of requests per window, in another order.
"""
from __future__ import annotations

import json
import math
import os
import random
from typing import Iterator, List

HERE = os.path.dirname(os.path.abspath(__file__))
BLOCK = 256


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        t = json.load(f)
    if t["loop"] not in ("closed", "open"):
        raise ValueError(f"traffic {name}: loop is {t['loop']!r}")
    if t.get("window", "running") not in ("running", "drained") or (
            t.get("window") == "drained" and t["loop"] != "closed"):
        raise ValueError(f"traffic {name}: window is {t['window']!r}")
    return t


def gap_block(rate_per_s: float, seed: int, block: int) -> List[float]:
    q = [-math.log(1.0 - (i + 0.5) / BLOCK) for i in range(BLOCK)]
    scale = (BLOCK / rate_per_s) / sum(q)
    gaps = [g * scale for g in q]
    random.Random(f"{int(seed)}/gaps/{block}").shuffle(gaps)
    return gaps


def arrivals(rate_per_s: float, seed: int) -> Iterator[float]:
    """Offsets in seconds from the start of the stream, without end."""
    t, block = 0.0, 0
    while True:
        for g in gap_block(rate_per_s, seed, block):
            t += g
            yield t
        block += 1
