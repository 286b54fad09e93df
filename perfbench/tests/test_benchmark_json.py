"""BENCHMARK.json against the files it names: every cell's configuration,
traffic mix and limits are there, every per-layer metric has its reader and
says the same as its file, and moves an end-to-end metric that each of its
cells reports."""
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "perfbench")
B = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def test_cells_find_their_files():
    configs = {c["name"]: c for c in B["configs"]}
    for w in B["workloads"]:
        c = configs[w["config"]]
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        for key in c["reduced"]:
            assert key in cfg, (c["name"], key)
        t = json.load(open(os.path.join(BENCH, "traffic",
                                        w["traffic"] + ".json")))
        assert t["loop"] in ("closed", "open")
        assert t.get("window", "running") in ("running", "drained")
        assert t.get("window") != "drained" or t["loop"] == "closed"
        lim = json.load(open(os.path.join(BENCH, "limits",
                                          w["name"] + ".json")))
        assert lim["limits"] and lim["sample_evals"] > 0
        assert w["chips"] == 1 and len(w["why"]) <= 200
    assert {c["name"] for c in B["configs"]} == \
        {w["config"] for w in B["workloads"]}


def test_every_cell_reports_setup_another_metric_and_a_layer():
    for w in B["workloads"]:
        e2e = [m["name"] for m in B["end_to_end"]
               if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in e2e and len(e2e) >= 2
        for name in e2e:
            assert os.path.exists(os.path.join(BENCH, "end_to_end",
                                               name + ".py"))
        assert any(w["name"] in m["workloads"] for m in B["per_layer"])


def test_per_layer_metrics_agree_with_their_files():
    e2e = {m["name"]: m for m in B["end_to_end"]}
    cells = {w["name"] for w in B["workloads"]}
    for m in B["per_layer"]:
        spec = json.load(open(os.path.join(BENCH, "metrics",
                                           m["name"] + ".json")))
        for k in ("name", "unit", "better", "source", "layer", "moves",
                  "workloads"):
            assert spec[k] == m[k], (m["name"], k)
        assert os.path.exists(os.path.join(BENCH, "readers",
                                           spec["reader"] + ".py"))
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        moved = e2e[m["moves"]]
        for w in m["workloads"]:
            assert w in cells
            assert w in moved.get("workloads", [w])


def test_no_metric_file_is_left_without_its_entry():
    for kind, listed in (("metrics", B["per_layer"]),
                         ("end_to_end", B["end_to_end"])):
        names = {os.path.splitext(f)[0]
                 for f in os.listdir(os.path.join(BENCH, kind))
                 if f.endswith((".json", ".py"))}
        assert names == {m["name"] for m in listed}, kind


def test_bounds_and_limits_of_the_contract():
    assert 1 <= B["run_seconds"] <= 51
    for m in B["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert len(json.dumps(B)) < 64 * 1024
