"""Evals that reached `complete` (and whose job read back whole) inside the
window, over the window's whole length."""
import stats


def compute(run: dict):
    return stats.rate((r.done for r in run["reqs"] if r.spec["_ok"]),
                      run["t0"], run["t1"])
