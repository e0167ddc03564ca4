"""The Makefile, the CI workflow and the documents name only files that
exist.  A deleted tool must take its target, its CI step and its
mentions with it: a gate nobody can run, or a document that sends the
reader to a file that is gone, is what this holds off."""

from __future__ import annotations

import functools
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# A path ending in .py; not the tail of a glob (`tools/*_smoke.py`) or
# of a placeholder (`deployments/<kind>.py`).
PY_PATH = re.compile(r"(?<![\w./*>-])([\w./-]*\w\.py)\b")
# Where the documents' paths are relative to.
ROOTS = ("", "pilosa_tpu", "tools", "tests", "benchmarks")

DOCUMENTS = sorted(
    ["README.md", "BASELINE.md", ".claude/skills/verify/SKILL.md"]
    + [
        os.path.relpath(p, REPO)
        for p in glob.glob(os.path.join(REPO, "docs", "*.md"))
    ]
)


def _read(rel: str) -> str:
    with open(os.path.join(REPO, rel), encoding="utf-8") as f:
        return f.read()


@functools.cache
def _basenames() -> frozenset[str]:
    """Every .py file name at the root and under ROOTS: what a document
    may name without a directory (`gameday.py`)."""
    names = {f for f in os.listdir(REPO) if f.endswith(".py")}
    for root in ROOTS[1:]:
        for _dir, _subdirs, files in os.walk(os.path.join(REPO, root)):
            names.update(f for f in files if f.endswith(".py"))
    return frozenset(names)


def _exists(path: str) -> bool:
    if "/" not in path:
        return path in _basenames()
    return any(
        os.path.isfile(os.path.join(REPO, root, path)) for root in ROOTS
    )


def _makefile_targets() -> dict[str, list[str]]:
    """target -> the .py scripts its recipe lines run."""
    targets: dict[str, list[str]] = {}
    current = None
    for line in _read("Makefile").splitlines():
        m = re.match(r"^([A-Za-z][\w-]*):(?!=)", line)
        if m:
            current = m.group(1)
            targets[current] = []
        elif line.startswith("\t") and current is not None:
            targets[current] += PY_PATH.findall(line)
        elif line.strip() and not line.startswith("#"):
            current = None
    return targets


def test_every_script_a_makefile_recipe_runs_exists():
    targets = _makefile_targets()
    assert "check" in targets and "test" in targets
    missing = {
        (target, script)
        for target, scripts in targets.items()
        for script in scripts
        if not os.path.isfile(os.path.join(REPO, script))
    }
    assert not missing, missing
    phony = re.search(r"^\.PHONY:(.*)$", _read("Makefile"), re.M).group(1)
    assert not set(phony.split()) - set(targets)


def test_every_make_step_of_ci_is_a_makefile_target_whose_scripts_exist():
    targets = _makefile_targets()
    steps = re.findall(
        r"^\s*run:.*?\bmake\s+([\w-]+)",
        _read(".github/workflows/check.yml"),
        re.M,
    )
    assert "check" in steps
    for step in steps:
        assert step in targets, f"check.yml runs `make {step}`: no such target"
        for script in targets[step]:
            assert os.path.isfile(os.path.join(REPO, script)), (step, script)


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_python_path_a_document_names_exists(document):
    missing = sorted(
        {p for p in PY_PATH.findall(_read(document)) if not _exists(p)}
    )
    assert not missing, f"{document} names files that do not exist: {missing}"
