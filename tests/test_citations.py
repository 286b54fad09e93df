"""Guard against phantom intra-repo citations.

Rounds 2-4 each shipped one docstring that cited a `nomad_tpu/...` path
that did not exist (scale-route comment, devicemanager, kernels/scoring).
This test greps every backtick-quoted or bare `nomad_tpu/...py` citation
in repo sources and asserts the file exists; a second case holds the
documents that say how to build, run and measure to the same rule.
"""
import re
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
CITE = re.compile(r"nomad_tpu/[A-Za-z0-9_/]+\.(?:py|cpp|c|h)")


def test_all_repo_path_citations_resolve():
    missing = []
    roots = [REPO / "nomad_tpu", REPO / "tests",
             REPO / "__graft_entry__.py"]
    for root in roots:
        files = [root] if root.is_file() else sorted(root.rglob("*.py"))
        for f in files:
            text = f.read_text(errors="replace")
            for m in CITE.finditer(text):
                if not (REPO / m.group(0)).exists():
                    missing.append(f"{f.relative_to(REPO)}: {m.group(0)}")
    assert not missing, (
        "phantom repo citations (file does not exist):\n" + "\n".join(missing))


#: the documents a newcomer follows; PERF.md, ROADMAP.md and CHANGES.md
#: are history and may name what is gone
DOCS = ["README.md", "COVERAGE.md", ".claude/skills/verify/SKILL.md"]
#: any `….py` name, with or without directories (`a_{b,c}.py` and
#: `test_*.py` patterns are not names and do not match)
DOC_PY = re.compile(
    r"(?<![\w/.*-])((?:[A-Za-z0-9_.-]+/)*[A-Za-z0-9_]+\.py)\b")
#: what a path in a document may be relative to
BASES = ["", "nomad_tpu", "tests"]


def test_documents_name_only_files_that_exist():
    """Every top-level script and every `perfbench/…py` / `nomad_tpu/…py`
    path README, COVERAGE and the verify skill name exists: a path from
    the root, `nomad_tpu/` or `tests/`; a bare name as a file of the
    tree. Keeps a deleted entry point out of the run recipes."""
    tree = {p.name for d in ("nomad_tpu", "tests", "perfbench")
            for p in (REPO / d).rglob("*.py")}
    tree |= {p.name for p in REPO.glob("*.py")}
    missing = []
    for doc in DOCS:
        for m in DOC_PY.finditer((REPO / doc).read_text(errors="replace")):
            path = m.group(1)
            if "/" in path:
                ok = any((REPO / b / path).exists() for b in BASES)
            else:
                ok = path in tree
            if not ok:
                missing.append(f"{doc}: {path}")
    assert not missing, (
        "documents name files that do not exist:\n"
        + "\n".join(sorted(set(missing))))
