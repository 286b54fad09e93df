"""BENCHMARK.json against the files it names: every cell's configuration,
traffic mix and limits are there, every per-layer metric has its reader and
says the same as its file, and moves an end-to-end metric that each of its
cells reports. Every check runs twice: on the tree as committed, and on a
copy of it to which a fifth cell was added the way a later `model_config`
PR has to add one — new files, new entries and longer `workloads` lists in
BENCHMARK.json, and no file of `perfbench/` that was there edited."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

import run as harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIFTH = "fifth-600.flood"


def files_under(root):
    """{relative path: SHA-256} of the benchmark's files under `root`."""
    out = {}
    for d, dirs, files in os.walk(os.path.join(root, "perfbench")):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for fn in files:
            p = os.path.join(d, fn)
            with open(p, "rb") as f:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def add_a_fifth_cell(root):
    """What a `model_config` PR brings, into a copy of the tree at `root`:
    a configuration's file (the test data's, whose kinds no code names), a
    traffic file, a limits file; in BENCHMARK.json a configuration, a cell,
    and the cell's name at the end of the `workloads` list of every metric
    the three floods share. -> the names of the files it added."""
    def load(*parts):
        with open(os.path.join(ROOT, *parts)) as f:
            return json.load(f)

    def dump(obj, *parts):
        with open(os.path.join(root, *parts), "w") as f:
            json.dump(obj, f, indent=1)
        return os.path.join(*parts)

    shutil.copytree(os.path.join(ROOT, "perfbench"),
                    os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name in ("nomad_tpu", "native"):    # the program: as it stands
        os.symlink(os.path.join(ROOT, name), os.path.join(root, name))
    cfg = load("perfbench", "testdata", "files-only", "config.json")
    # asks so small that the one named node holds every job sent to it
    cfg.update(name="fifth-600", source="none: test data", rehearsal={},
               job={"cpu": [2, 3, 5], "memory": [2, 3, 5], "disk": 1})
    traffic = load("perfbench", "traffic", "flood96-drained.json")
    traffic["why"] = "test data: flood96-drained under a new name"
    added = [
        dump(cfg, "perfbench", "configs", "fifth-600.json"),
        dump(traffic, "perfbench", "traffic", "fifth48.json"),
        dump(load("perfbench", "testdata", "files-only", "limits.json"),
             "perfbench", "limits", FIFTH + ".json")]
    bench = load("BENCHMARK.json")
    floods = next(m["workloads"] for m in bench["end_to_end"]
                  if m["name"] == "placements_per_s")[:]
    bench["configs"].append({
        "name": "fifth-600", "source": "none: test data",
        "file": "perfbench/configs/fifth-600.json", "reduced": [],
        "why": "test data: a deployment that is files alone"})
    bench["workloads"].append({
        "name": FIFTH, "config": "fifth-600", "traffic": "fifth48",
        "chips": 1, "why": "test data: the fifth cell, added by files"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if set(floods) <= set(m.get("workloads", ())):
            m["workloads"].append(FIFTH)
    dump(bench, "BENCHMARK.json")
    return added


@pytest.fixture(scope="module")
def fifth(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("fifth"))
    return root, add_a_fifth_cell(root)


@pytest.fixture(params=["as committed", "with a fifth cell"])
def tree(request, fifth):
    """(root of the tree, its BENCHMARK.json)"""
    root = ROOT if request.param == "as committed" else fifth[0]
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return root, json.load(f)


def bench_file(root, *parts):
    with open(os.path.join(root, "perfbench", *parts)) as f:
        return json.load(f)


def test_cells_find_their_files(tree):
    root, B = tree
    configs = {c["name"]: c for c in B["configs"]}
    for w in B["workloads"]:
        c = configs[w["config"]]
        with open(os.path.join(root, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        for key in c["reduced"]:
            assert key in cfg, (c["name"], key)
        t = bench_file(root, "traffic", w["traffic"] + ".json")
        assert t["loop"] in ("closed", "open")
        assert t.get("window", "running") in ("running", "drained")
        assert t.get("window") != "drained" or t["loop"] == "closed"
        lim = bench_file(root, "limits", w["name"] + ".json")
        assert lim["limits"] and lim["sample_evals"] > 0
        assert w["chips"] == 1 and len(w["why"]) <= 200
    assert {c["name"] for c in B["configs"]} == \
        {w["config"] for w in B["workloads"]}


def test_every_cell_reports_setup_another_metric_and_a_layer(tree):
    root, B = tree
    for w in B["workloads"]:
        e2e = [m["name"] for m in harness.metrics_of(B, w["name"], 0)]
        assert "setup_s" in e2e and len(e2e) >= 2
        for name in e2e:
            assert os.path.exists(os.path.join(root, "perfbench",
                                               "end_to_end", name + ".py"))
        assert harness.metrics_of(B, w["name"], 1)


def test_per_layer_metrics_agree_with_their_files(tree):
    """Key for key, but for `workloads`: where a metric is read is said by
    the entry's list in BENCHMARK.json alone, which a later PR may make
    longer; its file says how it is read, names no cell, and is never
    edited for a new one."""
    root, B = tree
    e2e = {m["name"]: m for m in B["end_to_end"]}
    cells = {w["name"] for w in B["workloads"]}
    for m in B["per_layer"]:
        spec = bench_file(root, "metrics", m["name"] + ".json")
        for k in ("name", "unit", "better", "source", "layer", "moves"):
            assert spec[k] == m[k], (m["name"], k)
        assert "workloads" not in spec, m["name"]
        assert os.path.exists(os.path.join(root, "perfbench", "readers",
                                           spec["reader"] + ".py"))
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        moved = e2e[m["moves"]]
        for w in m["workloads"]:
            assert w in cells
            assert w in moved.get("workloads", [w])


def test_one_entry_a_name_and_moved_metric(tree):
    """No family a cell: two entries that read the same thing the same way
    and move the same end-to-end metric are one entry with both cells in
    its `workloads` (PR 34 folded 128 entries into 73)."""
    root, B = tree
    assert len(B["per_layer"]) <= 128
    seen = {}
    for m in B["per_layer"]:
        spec = bench_file(root, "metrics", m["name"] + ".json")
        how = json.dumps({k: v for k, v in spec.items()
                          if k not in ("name", "reads")}, sort_keys=True)
        assert how not in seen, (m["name"], seen[how])
        seen[how] = m["name"]


def test_no_metric_file_is_left_without_its_entry(tree):
    root, B = tree
    for kind, listed in (("metrics", B["per_layer"]),
                         ("end_to_end", B["end_to_end"])):
        names = {os.path.splitext(f)[0]
                 for f in os.listdir(os.path.join(root, "perfbench", kind))
                 if f.endswith((".json", ".py"))}
        assert names == {m["name"] for m in listed}, kind


def test_bounds_and_limits_of_the_contract(tree):
    _, B = tree
    assert 1 <= B["run_seconds"] <= 51
    for m in B["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert len(json.dumps(B)) < 64 * 1024


def test_a_fifth_cell_is_new_files_and_longer_lists(fifth):
    """The copy with the fifth cell holds every file the benchmark had,
    byte for byte, and three more; its BENCHMARK.json is the committed one
    with entries added and lists made longer, nothing changed or taken
    away; and the harness gives the new cell every metric whose list names
    it, each with its file and its reader."""
    root, added = fifth
    before, after = files_under(ROOT), files_under(root)
    assert {p: after[p] for p in before} == before
    assert sorted(set(after) - set(before)) == sorted(added)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        B = json.load(f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        B5 = json.load(f)
    assert set(B5) == set(B)
    for key, was in B.items():
        if not isinstance(was, list) or key in ("command", "paths"):
            assert B5[key] == was, key
            continue
        assert len(B5[key]) >= len(was), key
        for e, now in zip(was, B5[key]):
            if FIFTH in now.get("workloads", ()):
                e = dict(e, workloads=e["workloads"] + [FIFTH])
            assert now == e, (key, e["name"])
    shared = harness.metrics_of(B, "pinned-10k.flood", 1)
    mine = harness.metrics_of(B5, FIFTH, 1)
    assert 30 <= len(mine) < len(shared)
    assert {m["name"] for m in mine} < {m["name"] for m in shared}
    for m in mine:
        spec = bench_file(root, "metrics", m["name"] + ".json")
        assert callable(harness.plugin("readers", spec["reader"]).read)
    assert [m["name"] for m in harness.metrics_of(B5, FIFTH, 0)] == \
        ["placements_per_s", "setup_s"]


def test_the_fifth_cell_runs_from_its_files(fifth):
    """A rehearsal of the added cell from the copy: the harness finds its
    configuration, kinds, traffic and limits by the names in BENCHMARK.json
    and judges what the program placed: `correct`."""
    root, _ = fifth
    p = subprocess.run(
        [sys.executable, os.path.join(root, "perfbench", "run.py"),
         "--workload", FIFTH, "--seed", str(2**31 + 34), "--seconds", "4",
         "--trace", "0", "--rehearsal"],
        cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    result, record = json.loads(lines[-1]), json.loads(lines[-2])
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(record["by_kind"]) == {"binpack", "one-partition",
                                      "named-node"}
