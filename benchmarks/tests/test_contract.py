"""``BENCHMARK.json`` against the limits a driver refuses it over before
any run: key sets, names, units, lengths, files under ``paths``."""

import ast
import glob
import json
import os
import re

import pytest

import run
from conftest import BENCH, FIXTURES, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    path = os.path.join(ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    with open(path) as f:
        return json.load(f)


def line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks"]
    assert bench["command"] == ["python3", "benchmarks/run.py"]
    rs = bench["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check with 24 cells has to fit
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200


def test_configs_and_cells(bench):
    names = set()
    files = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] not in names
        names.add(c["name"])
        assert line(c["source"]) and line(c["why"])
        assert c["file"].startswith("benchmarks/") and c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            doc = json.load(f)
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and key in doc and key in doc["reduced"]
            assert not key.endswith(("_dim", "_rank", "width"))
    cells, pairs = set(), set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["name"] not in cells
        cells.add(w["name"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert w["config"] in names and w["chips"] in (1, 4) and line(w["why"])
        assert os.path.exists(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    assert {w["config"] for w in bench["workloads"]} == names
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(cells) // 2)


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    seen = set()
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and line(m["layer"])
        assert m["moves"] in e2e
        moved = e2e[m["moves"]].get("workloads", cells)
        assert set(m.get("workloads", cells)) <= set(moved)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and m["name"] not in seen
        seen.add(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for c in cells:
        mine = [m for m in bench["end_to_end"] if c in m.get("workloads", cells)]
        assert len(mine) >= 2 and any(m["name"] == "setup_s" for m in mine)
        assert any(c in m.get("workloads", cells) for m in bench["per_layer"])


def test_files_under_paths_are_named_from_a_names_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for d, _dirs, files in os.walk(BENCH):
        if "__pycache__" in d:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(d, f), ROOT)
            assert ok.match(rel) and len(rel) <= 200, rel


# -- deployment kinds ---------------------------------------------------------

KIND_DIRS = {"shipped": os.path.join(BENCH, "deployments"),
             "fixture": os.path.join(FIXTURES, "deployments")}
KINDS = sorted((where, os.path.basename(path)[:-3])
               for where, d in KIND_DIRS.items()
               for path in glob.glob(os.path.join(d, "*.py")))


def test_a_configurations_kind_names_a_file_under_deployments(bench):
    assert (("shipped", run.DEFAULT_KIND) in KINDS
            and any(where == "fixture" for where, _ in KINDS))
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            kind = json.load(f).get("kind", run.DEFAULT_KIND)
        assert NAME.match(kind)
        assert os.path.isfile(os.path.join(KIND_DIRS["shipped"], kind + ".py")), kind


@pytest.mark.parametrize("where,name", KINDS)
def test_a_kind_exposes_the_seven_parts(where, name):
    kind = run.load_kind(name, KIND_DIRS[where])  # schema .. SITES: load_kind checks
    assert callable(kind.schema) and callable(kind.normalise)
    # data and load, the reference, the control
    for method in ("units", "make", "seal", "answer", "apply", "readback"):
        assert callable(getattr(kind.Reference, method)), method
    # the requests: what Load drives
    for method in ("warmup_rounds", "read", "schedule"):
        assert callable(getattr(kind.Traffic, method)), method
    assert kind.CONTROLS and all(NAME.match(c) for c in kind.CONTROLS)
    assert kind.SITES and all(isinstance(s, str) for s in kind.SITES)
    assert "hosteval" not in kind.SITES


@pytest.mark.parametrize("where,name", KINDS)
def test_a_kind_imports_nothing_of_the_program(where, name):
    with open(os.path.join(KIND_DIRS[where], name + ".py")) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
        elif isinstance(node, ast.Call) and getattr(node.func, "id", "") == "__import__":
            imported.add("__import__")
    # Not the program, and no way round the look: no import by a computed
    # name, no child process, no word with the server.
    assert not imported & {"pilosa_tpu", "__import__", "importlib", "runpy",
                           "subprocess", "socket", "http", "urllib"}, imported
    assert "numpy" in imported
