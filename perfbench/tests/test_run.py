"""The two-process command on the CPU: a rehearsal of each cell prints
counts and `correct` and no device metric; with the timed path broken
underneath, `correct` comes out false; without a TPU nothing is measured."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RUN = os.path.join(ROOT, "perfbench", "run.py")
CELLS = [w["name"] for w in json.load(open(os.path.join(
    ROOT, "BENCHMARK.json")))["workloads"]]


def run(workload, *flags, seed=2**31 + 29):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, RUN, "--workload", workload, "--seed", str(seed),
         "--seconds", "4", "--trace", "0", *flags],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    return p, lines


@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal_of_each_cell(workload):
    p, lines = run(workload, "--rehearsal")
    assert p.returncode == 0, p.stderr[-2000:]
    result = json.loads(lines[-1])
    assert list(result)[-1] == "checks"
    assert result["rehearsal"] is True and result["metrics"] == {}
    assert result["device"]["platform"] == "cpu"
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    record = json.loads(lines[-2])
    assert record["observe_lost"] == 0
    assert set(record["fill_at_end"]) == {"cpu", "memory", "disk"}
    assert "compiles_in_window" in record
    traffic = next(w["traffic"] for w in json.load(open(os.path.join(
        ROOT, "BENCHMARK.json")))["workloads"] if w["name"] == workload)
    kind = json.load(open(os.path.join(
        ROOT, "perfbench", "traffic", traffic + ".json"))).get(
            "window", "running")
    assert record["window"] == kind
    if kind == "drained":  # whole jobs only: all that was sent, answered
        assert record["completed_in_window"] == record["jobs_in_window"] \
            >= record["attempted"]
    # every number compared stands beside its limit at the end of stderr
    tail = p.stderr.strip().splitlines()[-len(result["checks"]):]
    assert all(ln.startswith("perfbench: check ") for ln in tail)


def test_the_control_goes_through_the_same_comparison_and_fails_it():
    p, lines = run("c1m-5k.flood", "--rehearsal", "--control")
    assert p.returncode == 0, p.stderr[-2000:]
    assert json.loads(lines[-1])["correct"] is True
    control = json.loads(lines[-2])["control"]
    assert control["correct"] is False
    assert set(control["checks"]) == {"score_gap_max", "score_dev_max_pct",
                                      "infeasible"}
    assert "perfbench: control score_gap_max" in p.stderr


@pytest.mark.parametrize("fault,fails", [
    ("wrong-node", ("score_gap_max", "infeasible")),
    ("half-left-out", ("short_with_room",)),
])
def test_a_fault_under_the_timed_path_is_not_correct(fault, fails):
    p, lines = run("c1m-5k.flood", "--rehearsal", "--fault", fault)
    assert p.returncode == 0, p.stderr[-2000:]
    result = json.loads(lines[-1])
    assert result["correct"] is False
    assert any(result["checks"][k]["value"] > result["checks"][k]["limit"]
               for k in fails), result["checks"]
    if fault == "half-left-out":
        assert result["failed"] == result["attempted"] > 0


def test_no_tpu_no_number():
    p, lines = run("c1m-5k.singles")   # no --rehearsal, platform cpu
    assert p.returncode != 0
    assert lines == []
