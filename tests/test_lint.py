"""nomadlint (nomad_tpu/analysis) — tier-1 gate + analyzer unit tests.

`test_tree_has_no_new_findings` is the ratchet: it runs the analyzer
over the whole package against the committed `lint_baseline.json`, so
any NEW JAX-purity or thread-safety violation fails tier-1. Everything
else pins the analyzer itself: fixture files with known violations
(exact rule ids + line numbers, via trailing `# NLxxx` markers), clean
near-miss fixtures, the baseline ratchet mechanics, the CLI exit
codes, and the regression tests for the findings this PR burned down.
"""
import ast
import os
import re
import shutil

from nomad_tpu.analysis import (Finding, compare_to_baseline,
                                load_baseline, run_tree, write_baseline)
from nomad_tpu.analysis.core import analyze_file, baseline_key
from nomad_tpu.analysis.jax_rules import collect_jit_registry
from nomad_tpu.analysis.__main__ import main as lint_main

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "nomad_tpu")
BASELINE = os.path.join(REPO, "lint_baseline.json")
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "lint_fixtures")

_MARKER = re.compile(r"#\s*(NL[A-Z]\d\d)\b")

_TREE_CACHE = []


def _scope_rel(*parts):
    """Synthetic repo-relative path mapping a fixture into a rule
    scope — assembled at runtime so the citations checker does not
    read these as real repo paths."""
    return "/".join(("nomad_tpu",) + parts)


def _tree_findings():
    """run_tree(PKG) once per session — several tests consume it, and
    tier-1 runs against a hard wall-clock budget."""
    if not _TREE_CACHE:
        _TREE_CACHE.append(run_tree(PKG))
    return _TREE_CACHE[0]


def _expected_markers(path):
    out = set()
    with open(path) as f:
        for i, line in enumerate(f, start=1):
            for rule in _MARKER.findall(line):
                out.add((rule, i))
    return out


def _analyze_fixture(name, rel):
    """Analyze one fixture under a scope-mapping repo-relative path."""
    path = os.path.join(FIXTURES, name)
    with open(path) as f:
        tree = ast.parse(f.read(), filename=rel)
    registry = {}
    collect_jit_registry(tree, registry)
    return analyze_file(path, rel, jit_registry=registry, tree=tree)


# ---- fixtures: exact rule ids and line numbers ----

def test_jax_fixture_findings_exact():
    found = _analyze_fixture("fixture_jax_violations.py",
                             _scope_rel("kernels", "fixture.py"))
    assert {(f.rule, f.line) for f in found} == _expected_markers(
        os.path.join(FIXTURES, "fixture_jax_violations.py"))


def test_thread_fixture_findings_exact():
    found = _analyze_fixture("fixture_thread_violations.py",
                             _scope_rel("server", "fixture.py"))
    assert {(f.rule, f.line) for f in found} == _expected_markers(
        os.path.join(FIXTURES, "fixture_thread_violations.py"))


def test_clean_fixtures_have_zero_findings():
    assert _analyze_fixture("fixture_jax_clean.py",
                            _scope_rel("kernels", "fixture_clean.py")) == []
    assert _analyze_fixture("fixture_thread_clean.py",
                            _scope_rel("server", "fixture_clean.py")) == []


# ---- ISSUE 14 families: lock discipline, device discipline, vocab ----
# Each violation fixture is pinned EXACTLY (rule ids + line numbers via
# trailing markers); each clean fixture is the same shape with the
# discipline applied and must be silent. Scope mapping: the lock
# fixtures sit OUTSIDE the NLT01-03 thread scope (raft/) so only the
# interprocedural family fires; the device fixtures impersonate the
# fused-dispatch module (scheduler/stack.py) to be in TRANSFER/DONATE/
# WAVE scope.

def test_lock_fixture_findings_exact():
    found = _analyze_fixture("fixture_lock_violations.py",
                             _scope_rel("raft", "fixture.py"))
    assert {(f.rule, f.line) for f in found} == _expected_markers(
        os.path.join(FIXTURES, "fixture_lock_violations.py"))


def test_lock_cycle_reports_full_path():
    """The seeded three-lock cycle must render the WHOLE cycle (all
    three locks, back to the start) plus a per-edge witness call site —
    the 'reading a lock-order finding' contract in README."""
    found = _analyze_fixture("fixture_lock_violations.py",
                             _scope_rel("raft", "fixture.py"))
    cycles = [f for f in found if f.rule == "NLT04"
              and "ThreeLockCycle" in f.message]
    assert len(cycles) == 1
    msg = cycles[0].message
    assert ("ThreeLockCycle.la -> ThreeLockCycle.lb -> "
            "ThreeLockCycle.lc -> ThreeLockCycle.la") in msg
    # each hop carries its witness (function + file:line)
    for hop in ("ThreeLockCycle.ab", "ThreeLockCycle.bc",
                "ThreeLockCycle.ca"):
        assert hop in msg
    # the call-mediated module-lock cycle is a separate finding whose
    # edges only exist through the resolved call tree
    mod = [f for f in found if f.rule == "NLT04" and "M_A" in f.message]
    assert len(mod) == 1
    assert "via _grab_b()" in mod[0].message


def test_device_fixture_findings_exact():
    found = _analyze_fixture("fixture_device_violations.py",
                             _scope_rel("scheduler", "stack.py"))
    assert {(f.rule, f.line) for f in found} == _expected_markers(
        os.path.join(FIXTURES, "fixture_device_violations.py"))


def test_vocab_fixture_findings_exact():
    found = _analyze_fixture("fixture_vocab_violations.py",
                             _scope_rel("lib", "fixture.py"))
    assert {(f.rule, f.line) for f in found} == _expected_markers(
        os.path.join(FIXTURES, "fixture_vocab_violations.py"))


def test_new_family_clean_fixtures_are_silent():
    assert _analyze_fixture("fixture_lock_clean.py",
                            _scope_rel("raft", "fixture_clean.py")) == []
    assert _analyze_fixture("fixture_device_clean.py",
                            _scope_rel("scheduler", "stack.py")) == []
    assert _analyze_fixture("fixture_vocab_clean.py",
                            _scope_rel("lib", "fixture_clean.py")) == []


# ---- ISSUE 16 families: replica determinism (NLR) + secret taint ----
# Scope mapping: raft/ keeps the fixtures outside the NLT01-03 thread
# scope, so only the new families (plus the lock family, silent here)
# run. The NLR scope is self-computed from each fixture's own
# ALLOWED_OPS literal / Fsm class, not from the path.

def test_replica_fixture_findings_exact():
    found = _analyze_fixture("fixture_replica_violations.py",
                             _scope_rel("raft", "fixture_replica.py"))
    assert {(f.rule, f.line) for f in found} == _expected_markers(
        os.path.join(FIXTURES, "fixture_replica_violations.py"))


def test_secret_fixture_findings_exact():
    found = _analyze_fixture("fixture_secret_violations.py",
                             _scope_rel("raft", "fixture_secret.py"))
    assert {(f.rule, f.line) for f in found} == _expected_markers(
        os.path.join(FIXTURES, "fixture_secret_violations.py"))


def test_replica_and_secret_clean_fixtures_are_silent():
    assert _analyze_fixture(
        "fixture_replica_clean.py",
        _scope_rel("raft", "fixture_replica_clean.py")) == []
    assert _analyze_fixture(
        "fixture_secret_clean.py",
        _scope_rel("raft", "fixture_secret_clean.py")) == []


def test_replica_finding_renders_full_apply_path():
    """An NLR01/02 report names the whole call path from the apply
    root to the entropy read (the 'reading a determinism finding'
    contract in README), and carries the hops as related locations
    for the SARIF emitter."""
    found = _analyze_fixture("fixture_replica_violations.py",
                             _scope_rel("raft", "fixture_replica.py"))
    leaf = next(f for f in found if f.rule == "NLR01"
                and "time.time" in f.message)
    assert "Store.upsert_eval [ALLOWED_OPS mutator on Store]" \
        in leaf.message
    assert "-> make_blocked_eval" in leaf.message
    assert leaf.related, "related locations feed SARIF"
    assert any("make_blocked_eval" in text
               for _rel, _line, text in leaf.related)


# ---- waivers ----

def test_waiver_with_reason_suppresses_and_is_counted(tmp_path):
    from nomad_tpu.analysis.core import _suppressions

    src = ("import threading\n"
           "import time\n"
           "class C:\n"
           "    def __init__(self, cb):\n"
           "        self.cb = cb\n"
           "        self._lk = threading.Lock()\n"
           "    def m(self):\n"
           "        with self._lk:\n"
           "            self.cb()  # nomadlint: ok NLT05 cb is a pure "
           "read, documented\n")
    p = tmp_path / "waived.py"
    p.write_text(src)
    stats = {}
    found = analyze_file(str(p), _scope_rel("raft", "waived.py"),
                         stats=stats)
    assert found == []
    waivers = stats["waivers"]
    assert len(waivers) == 1 and waivers[0].rule == "NLT05"
    assert waivers[0].used and waivers[0].reason.startswith("cb is")


def test_waiver_without_reason_is_a_finding(tmp_path):
    src = ("import threading\n"
           "class C:\n"
           "    def __init__(self, cb):\n"
           "        self.cb = cb\n"
           "        self._lk = threading.Lock()\n"
           "    def m(self):\n"
           "        with self._lk:\n"
           "            self.cb()  # nomadlint: ok NLT05\n")
    p = tmp_path / "bad_waiver.py"
    p.write_text(src)
    found = analyze_file(str(p), _scope_rel("raft", "bad_waiver.py"))
    rules = sorted(f.rule for f in found)
    # the reason-less waiver suppresses NOTHING and is itself flagged
    assert rules == ["NLT05", "NLW00"]


def test_inline_suppression(tmp_path):
    src = ("import jax\n"
           "@jax.jit\n"
           "def f(x):\n"
           "    return x.item()  # nomadlint: disable=NLJ01\n")
    p = tmp_path / "suppressed.py"
    p.write_text(src)
    assert analyze_file(str(p), _scope_rel("kernels", "supp.py")) == []


# ---- THE tier-1 ratchet ----

def test_tree_has_no_new_findings():
    new = compare_to_baseline(_tree_findings(), load_baseline(BASELINE))
    assert new == [], "NEW lint findings over lint_baseline.json:\n" \
        + "\n".join(f.render() for f in new)


def test_baseline_has_no_dead_entries():
    """Every baselined key still exists — burned-down findings must be
    REMOVED from the baseline, keeping the ratchet monotone."""
    live = {baseline_key(f) for f in _tree_findings()}
    dead = [k for k in load_baseline(BASELINE) if k not in live]
    assert dead == [], f"stale baseline entries (regenerate): {dead}"


def test_ratchet_fails_on_new_violation(tmp_path):
    """A newly introduced violation exceeds the frozen count and fails,
    while every baselined finding still passes."""
    findings = _tree_findings()
    baseline = load_baseline(BASELINE)
    assert compare_to_baseline(findings, baseline) == []
    extra = Finding("nomad_tpu/kernels/placement.py", 1, "NLJ05",
                    "injected", context="")
    assert compare_to_baseline(findings + [extra], baseline) == [extra]
    # and a SECOND instance of an already-baselined key also fails
    if findings:
        dupe = findings[0]
        assert dupe in compare_to_baseline(findings + [dupe], baseline)
    # write/load roundtrip freezes exactly the current counts
    p = tmp_path / "bl.json"
    write_baseline(str(p), findings + [extra])
    assert compare_to_baseline(findings + [extra],
                               load_baseline(str(p))) == []


# ---- CLI (the pre-commit gate) ----

def test_cli_fail_on_new_clean_then_dirty(tmp_path, capsys):
    """End-to-end CLI ratchet on a kernels-only copy (rel paths — and
    so baseline keys and hot-path scope — are preserved because the
    copy root is still named nomad_tpu; a subtree keeps this cheap
    enough for the wall-clock-bounded tier-1 run)."""
    dst = tmp_path / "nomad_tpu"
    shutil.copytree(os.path.join(PKG, "kernels"), dst / "kernels",
                    ignore=shutil.ignore_patterns("__pycache__"))
    argv = [str(dst), "--baseline", BASELINE, "--fail-on-new"]
    assert lint_main(argv) == 0
    # default mode on the same copy: lists findings, exits 0
    assert lint_main([str(dst)]) == 0
    assert "finding(s)" in capsys.readouterr().out
    # introduce a hot-path violation into the copy
    with open(dst / "kernels" / "placement.py", "a") as f:
        f.write("\n\ndef _lint_canary(x):\n"
                "    jax.debug.print(\"{}\", x)\n"
                "    return x\n")
    assert lint_main(argv) == 2
    out = capsys.readouterr().out
    assert "NLJ05" in out


def test_cli_explain_prints_rationale_and_fixture_example(capsys):
    assert lint_main(["--explain", "NLT04"]) == 0
    out = capsys.readouterr().out
    assert "lock-order inversion" in out
    assert "fix:" in out
    # the fixture suite provides the worked example
    assert "fixture_lock_violations.py" in out
    assert lint_main(["--explain", "NLX99"]) == 1


def test_cli_format_json_machine_readable(tmp_path, capsys):
    import json as _json

    src = ("import threading\nimport time\n"
           "class C:\n"
           "    def __init__(self):\n"
           "        self._lk = threading.Lock()\n"
           "    def m(self):\n"
           "        with self._lk:\n"
           "            self.m()\n")
    pkg = tmp_path / "nomad_tpu"
    pkg.mkdir()
    (pkg / "mod.py").write_text(src)
    assert lint_main([str(pkg), "--format", "json"]) == 0
    payload = _json.loads(capsys.readouterr().out)
    (f,) = payload["findings"]
    assert f["rule"] == "NLT05"
    assert f["file"].endswith("mod.py")
    assert f["line"] == 8
    assert f["context"] == "C.m"
    # --json stays as the legacy alias
    assert lint_main([str(pkg), "--json"]) == 0
    assert _json.loads(capsys.readouterr().out)["findings"]


def test_cli_format_sarif(tmp_path, capsys):
    """`--format sarif` emits a valid SARIF 2.1.0 run: driver rules
    from ALL_RULES, one result per finding with ruleId/level/location,
    and the NLR call path as relatedLocations."""
    import json as _json
    import shutil as _shutil

    src = os.path.join(FIXTURES, "fixture_replica_violations.py")
    pkg = tmp_path / "nomad_tpu" / "raft"
    pkg.mkdir(parents=True)
    _shutil.copy(src, pkg / "fixture_replica.py")
    assert lint_main([str(tmp_path / "nomad_tpu"),
                      "--format", "sarif"]) == 0
    out = capsys.readouterr().out
    doc = _json.loads(out)
    assert doc["version"] == "2.1.0"
    assert "sarif-2.1.0" in doc["$schema"]
    (run,) = doc["runs"]
    driver = run["tool"]["driver"]
    assert driver["name"] == "nomadlint"
    rule_ids = {r["id"] for r in driver["rules"]}
    assert {"NLR01", "NLR02", "NLR03", "NLR04", "NLS01"} <= rule_ids
    results = run["results"]
    assert results and all(r["level"] == "error" for r in results)
    expected = {(rule, line) for rule, line in _expected_markers(src)}
    got = {(r["ruleId"],
            r["locations"][0]["physicalLocation"]["region"]["startLine"])
           for r in results}
    assert got == expected
    # an interprocedural NLR finding carries its call path
    nlr01 = next(r for r in results if r["ruleId"] == "NLR01"
                 and "time.time" in r["message"]["text"])
    rel_locs = nlr01["relatedLocations"]
    assert rel_locs and all(
        rl["physicalLocation"]["artifactLocation"]["uri"]
        for rl in rel_locs)
    assert any("make_blocked_eval" in rl["message"]["text"]
               for rl in rel_locs)
    # no trailing human-readable summary pollutes the JSON document
    assert out.strip().endswith("}")


def test_cli_format_json_pins_unchanged_schema(tmp_path, capsys):
    """--format json output for the new families keeps the pinned
    shape (rule/file/line/context keys) — downstream tooling parses
    it; `related` stays SARIF-only."""
    import json as _json
    import shutil as _shutil

    src = os.path.join(FIXTURES, "fixture_secret_violations.py")
    pkg = tmp_path / "nomad_tpu" / "raft"
    pkg.mkdir(parents=True)
    _shutil.copy(src, pkg / "fixture_secret.py")
    assert lint_main([str(tmp_path / "nomad_tpu"),
                      "--format", "json"]) == 0
    payload = _json.loads(capsys.readouterr().out)
    assert payload["findings"]
    for f in payload["findings"]:
        assert f["rule"] == "NLS01"
        assert set(f) >= {"rule", "file", "line", "context", "message"}
        assert "related" not in f


def test_cli_duplicate_roots_do_not_double_count(tmp_path, capsys):
    """Passing overlapping/duplicate path args dedups findings AND the
    stats side: the waiver ledger merges by site and `files` counts
    each analyzed file once."""
    import json as _json

    src = ("import threading\n"
           "class C:\n"
           "    def __init__(self, cb):\n"
           "        self.cb = cb\n"
           "        self._lk = threading.Lock()\n"
           "    def m(self):\n"
           "        with self._lk:\n"
           "            self.cb()  # nomadlint: ok NLT05 pure read, "
           "documented\n")
    pkg = tmp_path / "nomad_tpu"
    pkg.mkdir()
    (pkg / "mod.py").write_text(src)
    assert lint_main([str(pkg), str(pkg), "--format", "json",
                      "--stats"]) == 0
    payload = _json.loads(capsys.readouterr().out)
    assert payload["findings"] == []
    assert payload["stats"]["files"] == 1
    assert payload["stats"]["by_rule"] == {}  # waived → nothing counted
    (w,) = payload["stats"]["waivers"]
    assert w["rule"] == "NLT05" and w["used"]


def test_cli_stats_lists_waiver_ledger(capsys):
    """--stats prints per-rule counts plus every waiver with its
    reason and active/stale state (the shipped tree carries the ISSUE
    14 burn-down waivers — they must all be ACTIVE)."""
    assert lint_main([PKG, "--stats"]) == 0
    out = capsys.readouterr().out
    assert "findings by rule: clean" in out
    assert "waivers:" in out
    assert "0 stale" in out
    assert "NO REASON" not in out


def test_analyzer_wall_clock_budget():
    """The whole analyzer (per-file rules + whole-program lock graph)
    must stay under 10s on the full tree — it gates pre-commit and runs
    in every PR's tier-1 suite (ISSUE 14 acceptance)."""
    import time as _time

    t0 = _time.monotonic()
    run_tree(PKG)
    assert _time.monotonic() - t0 < 10.0


# ---- regression: the findings this PR burned down stay fixed ----


def test_broker_estimator_discipline_holds():
    """PR 8's documented hazard, now a rule: the broker footprint
    estimator must never be invoked under the broker lock (its reads
    re-enter enqueue). The shipped _group_picks runs OUTSIDE the lock —
    NLT05 must be silent on broker.py — while the fixture pins that the
    pre-fix shape (callback under the owner's lock) is still caught."""
    found = [f for f in _tree_findings()
             if f.rule == "NLT05"
             and f.path == "nomad_tpu/server/broker.py"]
    assert found == [], [f.render() for f in found]
    fixture = _analyze_fixture("fixture_lock_violations.py",
                               _scope_rel("raft", "fixture.py"))
    assert any(f.rule == "NLT05"
               and f.context == "Reenter.estimate_under_lock"
               for f in fixture)


def test_wave_fold_stays_bitwise():
    """place_table_wave's lane-carry fold is the NLD04 contract: the
    shipped kernel folds by jnp.where selection (silent), and the rule
    catches the arithmetic fold in the fixture."""
    found = _tree_findings()
    assert not any(f.rule == "NLD04"
                   and f.path == "nomad_tpu/kernels/placement.py"
                   for f in found)
    fixture = _analyze_fixture("fixture_device_violations.py",
                               _scope_rel("scheduler", "stack.py"))
    assert any(f.rule == "NLD04" for f in fixture)

def test_task_runner_template_state_is_lock_guarded():
    """ADVICE.md r5 / satellite: _tmpl_content, _secret_data and
    _secret_env are shared by the run loop and the watcher thread —
    NLT01 must stay silent on them now that _tmpl_lock guards both
    sides, while the pre-fix shape (fixture WatcherRace) keeps being
    caught."""
    path = os.path.join(PKG, "client", "task_runner.py")
    found = analyze_file(path, "nomad_tpu/client/task_runner.py")
    contexts = {f.context for f in found if f.rule == "NLT01"}
    for attr in ("TaskRunner._tmpl_content", "TaskRunner._secret_data",
                 "TaskRunner._secret_env"):
        assert attr not in contexts, f"{attr} race reintroduced"
    # the rule itself still catches the pre-fix pattern
    fixture = _analyze_fixture("fixture_thread_violations.py",
                               _scope_rel("server", "fixture.py"))
    assert any(f.rule == "NLT01" and f.context == "WatcherRace._content"
               for f in fixture)


def test_task_runner_watcher_swallows_are_logged():
    path = os.path.join(PKG, "client", "task_runner.py")
    found = analyze_file(path, "nomad_tpu/client/task_runner.py")
    assert not any(f.rule == "NLT03"
                   and f.context == "TaskRunner._template_watch"
                   for f in found)


def test_preemption_kernel_is_scatter_and_gather_free():
    path = os.path.join(PKG, "kernels", "preemption.py")
    found = analyze_file(path, "nomad_tpu/kernels/preemption.py")
    assert not any(f.rule in ("NLJ06", "NLJ07") for f in found)


def test_eval_timestamps_stay_leader_minted():
    """ISSUE 16 burn-down: structs/evaluation.py no longer stamps
    `time.time()` inside replicated values (the `now` parameter rides
    the raft entry) — NLR01 must be silent on the tree while the
    fixture pins that the pre-fix shape is still caught."""
    found = [f for f in _tree_findings() if f.rule == "NLR01"]
    assert found == [], [f.render() for f in found]
    fixture = _analyze_fixture("fixture_replica_violations.py",
                               _scope_rel("raft", "fixture_replica.py"))
    assert any(f.rule == "NLR01" and f.context == "make_blocked_eval"
               for f in fixture)


def test_port_draws_stay_caller_seeded():
    """ISSUE 16 burn-down: structs/network.py requires a caller-seeded
    rng for stochastic port draws (zero-arg random.Random() raised
    NLR02 pre-fix) — silent on the tree, caught in the fixture."""
    found = [f for f in _tree_findings() if f.rule == "NLR02"]
    assert found == [], [f.render() for f in found]
    fixture = _analyze_fixture("fixture_replica_violations.py",
                               _scope_rel("raft", "fixture_replica.py"))
    assert any(f.rule == "NLR02" and f.context == "assign_ports"
               for f in fixture)


def test_secret_egress_stays_redacted():
    """The PR 10 node_get leak, now a rule: NLS01 silent on the tree
    (the two cli.py bootstrap prints carry reviewed waivers — the
    operator terminal IS the credential delivery channel), still
    caught in the fixture."""
    found = [f for f in _tree_findings() if f.rule == "NLS01"]
    assert found == [], [f.render() for f in found]
    fixture = _analyze_fixture("fixture_secret_violations.py",
                               _scope_rel("raft", "fixture_secret.py"))
    contexts = {f.context for f in fixture if f.rule == "NLS01"}
    assert {"Server.node_get", "Server.node_tree",
            "Server.debug_node"} <= contexts


def test_cursor_discipline_holds_on_stack():
    """scheduler/stack.py's certify path captures cluster versions
    before reading the delta logs — NLR04 silent on the tree, both
    pre-fix shapes (live read, late capture) caught in the fixture."""
    found = [f for f in _tree_findings() if f.rule == "NLR04"]
    assert found == [], [f.render() for f in found]
    fixture = _analyze_fixture("fixture_replica_violations.py",
                               _scope_rel("raft", "fixture_replica.py"))
    ctxs = {f.context for f in fixture if f.rule == "NLR04"}
    assert ctxs == {"scan_live_cursor", "scan_late_capture",
                    "certify_chain_interval"}


def test_analyzer_needs_no_jax_import():
    """Lint time must not pay (or require) a jax import — the CLI is a
    pre-commit gate that must run anywhere, fast."""
    import subprocess
    import sys
    code = (
        "import sys\n"
        "sys.modules['jax'] = None  # any `import jax` now raises\n"
        "from nomad_tpu.analysis.core import run_tree\n"
        "fs = run_tree(sys.argv[1])\n"
        "assert not any(f.rule.startswith('NLP') for f in fs), fs\n"
        "print('OK', len(fs))\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code, os.path.join(PKG, "kernels")],
        capture_output=True, text=True, cwd=REPO, timeout=60)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.startswith("OK")
