"""The documents a session starts from name only what the tree holds.

PR 30 retired the tunnel-era measuring code; the README, DEVELOP.md and
the verify skill went on citing it for five PRs before that.  These
tests fail for the next PR that deletes or renames a file and leaves a
document, a CI step, a comment or the table of environment variables
behind."""

import functools
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]

DOCS = ("README.md", "DEVELOP.md", ".claude/skills/verify/SKILL.md")

# a back-ticked token is read as a path of this repo when it starts with
# one of the tree's own top-level names, ends like a file of the kinds
# the tree holds, or ends in "/" (a directory)
_TOP = (
    "moose_tpu/", "scripts/", "tests/", "examples/", "tutorials/",
    "chipbench/", "docs/", ".github/", ".claude/",
)
_PATH_LIKE = re.compile(
    r"^[A-Za-z0-9_.\-]+(/[A-Za-z0-9_.\-]+)*"
    r"(/|\.(py|md|json|jsonl|toml|yml|cpp))$"
)
# placeholders and outputs a document may name without the tree holding them
_NOT_A_PATH = re.compile(r"[<>*{}$]|\.\.\.")
_WRITTEN_AT_RUN_TIME = {
    "your_driver.py", "model.onnx", "events.jsonl", "trace.json",
    "MANIFEST.json", "specs.json",  # a serving snapshot's own files
    "chiprun_out/",  # what a chip call brings back; ignored by git
}


def _paths_named(text: str):
    for token in re.findall(r"`([^`\n]+)`", text):
        token = token.strip()
        for word in token.split():
            word = word.rstrip(".,;:)").lstrip("(")
            # `file.py::test`, `file.py:12` and `file.py --flag` name file.py
            word = re.split(r"::|:\d", word)[0]
            if _NOT_A_PATH.search(word) or word in _WRITTEN_AT_RUN_TIME:
                continue
            if word.startswith(_TOP) or _PATH_LIKE.match(word):
                yield word


@functools.lru_cache(maxsize=None)
def _tree():
    """Every file and directory under the tree's own directories, as
    ``/``-led paths from the root."""
    return tuple(
        "/" + str(hit.relative_to(ROOT))
        for top in _TOP
        for hit in (ROOT / top).rglob("*")
    )


def _held(path: str) -> bool:
    """A path is taken from the root; one that does not start with a
    top-level directory (``dist_smoke.py`` for ``scripts/dist_smoke.py``,
    ``serving/registry.py``) may be the tail of a path anywhere under
    the tree's own directories."""
    if (ROOT / path).exists():
        return True
    if path.startswith(_TOP):
        return False
    tail = "/" + path.rstrip("/")
    return any(held.endswith(tail) for held in _tree())


@pytest.mark.parametrize("doc", DOCS)
def test_every_path_a_document_names_exists(doc):
    text = (ROOT / doc).read_text()
    named = sorted(set(_paths_named(text)))
    assert len(named) >= 10, f"{doc}: the reader found only {named}"
    missing = [p for p in named if not _held(p)]
    assert not missing, f"{doc} names paths the tree does not hold: {missing}"


def test_every_script_a_ci_step_runs_exists():
    text = (ROOT / ".github/workflows/ci.yml").read_text()
    ran = sorted(set(re.findall(r"\bpython3? +([\w./\-]+\.py)\b", text)))
    assert len(ran) >= 10, ran
    missing = [p for p in ran if not (ROOT / p).exists()]
    assert not missing, f"ci.yml runs files the tree does not hold: {missing}"


_VARIABLE = re.compile(r"MOOSE_TPU_[A-Z0-9_]+")


def _variables_the_program_reads():
    names = set()
    for path in (ROOT / "moose_tpu").rglob("*.py"):
        names |= set(_VARIABLE.findall(path.read_text()))
    # `MOOSE_TPU_CANARY_*` in prose: a family, not a name
    return {n for n in names if not n.endswith("_")}


def _variables_the_table_lists():
    names = set()
    for line in (ROOT / "DEVELOP.md").read_text().splitlines():
        if line.startswith("| `MOOSE_TPU_"):
            names |= set(_VARIABLE.findall(line.split("|")[1]))
    return names


def test_every_variable_the_program_reads_is_in_the_table():
    unlisted = _variables_the_program_reads() - _variables_the_table_lists()
    assert not unlisted, (
        f"DEVELOP.md's tables lack rows for {sorted(unlisted)}"
    )


def test_every_variable_in_the_table_is_read_by_the_program():
    unread = _variables_the_table_lists() - _variables_the_program_reads()
    assert not unread, (
        f"DEVELOP.md lists variables nothing under moose_tpu/ reads: "
        f"{sorted(unread)}"
    )


# the reference's own tree has a benchmarks/ directory too, and
# parallel/spmd.py cites its logreg script: that one stays
_RETIRED = re.compile(
    r"bench\.py|bench_gate|BENCH_r|benchmarks/(?!pymoose/)"
)


def test_no_code_cites_the_retired_measuring_system():
    cited = []
    for top in ("moose_tpu", "scripts", "examples", "tutorials"):
        for path in sorted((ROOT / top).rglob("*")):
            if not path.is_file() or path.suffix in (".pyc", ".so", ".o"):
                continue
            try:
                text = path.read_text()
            except UnicodeDecodeError:
                continue
            for n, line in enumerate(text.splitlines(), 1):
                if _RETIRED.search(line):
                    cited.append(f"{path.relative_to(ROOT)}:{n}")
    assert not cited, f"these lines cite files PR 30 deleted: {cited}"
