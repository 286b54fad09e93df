"""Allocations of the evals that completed inside the window, as read back,
over the window's whole length. In a drained window (`traffic.py`) that is
every job sent in it, over the time until the last of them was answered."""
import stats


def compute(run: dict):
    ok = [r for r in run["reqs"] if r.spec["_ok"]]
    return stats.rate((r.done for r in ok), run["t0"], run["t1"],
                      weights=(len(r.spec["_allocs"]) for r in ok))
