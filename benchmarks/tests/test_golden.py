"""``two-row-count`` against the parent tree: for a given seed the arrays
posted to ``/import`` and the request texts in order are those that
``reference.py`` and ``traffic.Traffic`` made at commit f6d618c, before
they moved behind the deployment kind.  The hashes in
``fixtures/golden-two-row-count.json`` were taken from that tree."""

import hashlib
import json
import os

import pytest

import run
from conftest import BENCH, HERE

with open(os.path.join(HERE, "fixtures", "golden-two-row-count.json")) as f:
    GOLDEN = json.load(f)["seeds"]
SEEDS = sorted(GOLDEN, key=int)
KIND = run.load_kind(run.DEFAULT_KIND)


def config(name):
    where = "tests/fixtures" if name == "tiny" else "configs"
    return run.read_json(os.path.join(BENCH, where, name + ".json"))


def mix(name):
    where = "tests/fixtures/traffic" if name == "rw-mix-tiny" else "traffic"
    return run.read_json(os.path.join(BENCH, where, name + ".json"))


def sha_arrays(*arrays):
    h = hashlib.sha256()
    for a in arrays:
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def sha_lines(lines):
    return hashlib.sha256("\n".join(lines).encode()).hexdigest()


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("where", sorted(GOLDEN[SEEDS[0]]["slices"]))
def test_a_slice_is_imported_as_the_parent_imported_it(where, seed):
    name, s = where.split("/")
    unit = KIND.Reference(config(name), int(seed)).make(int(s))
    assert (unit["route"], unit["slice"]) == ("import", int(s))
    assert (unit["index"], unit["frame"]) == ("segment", "f")
    assert GOLDEN[seed]["slices"][where] == {
        "bits": int(unit["rows"].size),
        "sha256": sha_arrays(unit["rows"], unit["cols"]),
    }


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("where", sorted(GOLDEN[SEEDS[0]]["texts"]))
def test_the_window_and_the_warm_up_send_the_parents_texts(where, seed):
    cname, mname = where.split("/")
    t = KIND.Traffic(mix(mname), config(cname), int(seed))
    texts = [t.read(i // t.clients if t.fixed else i, i % t.clients).text
             for i in range(500)]
    assert GOLDEN[seed]["texts"][where] == {
        "first": texts[0], "sha256": sha_lines(texts)}
    warm = ["|".join(r.text for r in rnd) for rnd in t.warmup_rounds()]
    assert GOLDEN[seed]["warmup"][where] == {
        "rounds": len(warm), "sha256": sha_lines(warm)}


@pytest.mark.parametrize("seed", SEEDS)
def test_an_open_loop_keeps_the_parents_schedule(seed):
    t = KIND.Traffic(mix("rw-mix-tiny"), config("tiny"), int(seed))
    plan = t.schedule(5.0)
    assert GOLDEN[seed]["schedule"]["tiny/rw-mix-tiny"] == {
        "requests": len(plan),
        "writes": sum(r.kind == "write" for r in plan),
        "sha256": sha_lines([f"{r.kind} {r.text} {r.key!r} {r.due!r}" for r in plan]),
    }
