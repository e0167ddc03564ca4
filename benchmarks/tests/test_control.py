"""The control at a size a test run holds: the reference with a
guarantee broken has to fail the comparison that decides ``correct``,
and the reference itself has to agree with a second plain count."""

import json
import os

import numpy as np
import pytest

import control
import run
from conftest import BENCH, HERE

KIND = run.load_kind(run.DEFAULT_KIND)
CONTROLS, OPS, Traffic = KIND.CONTROLS, KIND.OPS, KIND.Traffic


def tiny(seed=7):
    with open(os.path.join(HERE, "fixtures", "tiny.json")) as f:
        cfg = json.load(f)
    ref = KIND.Reference(cfg, seed)
    for unit in ref.units():
        ref.make(unit)
    ref.seal()
    return cfg, ref


def mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", [1, 2_147_483_700, 3_000_000_019])
@pytest.mark.parametrize("name", ["count-distinct", "count-repeat"])
@pytest.mark.parametrize("broken", CONTROLS)
def test_the_control_is_not_correct(seed, name, broken):
    """Through the comparison a run makes, not one of the test's own."""
    cfg, ref = tiny(seed)
    traffic = Traffic(mix(name), cfg, seed)
    sound = control.judge(ref, traffic, 40, None)
    assert sound["correct"] is True
    assert sound["compared"]["wrong_answers"] == {"value": 0, "limit": 0}
    assert sound["compared"]["answers_compared"]["value"] == 40
    out = control.judge(ref, traffic, 40, broken)
    assert out["correct"] is False
    assert out["compared"]["wrong_answers"]["value"] > 0  # the limit is 0
    assert out["compared"]["unanswered"]["value"] == 0


def test_the_reference_agrees_with_python_sets():
    cfg, ref = tiny()
    a, b = 0, 5
    x, y = set(ref._rows[a].tolist()), set(ref._rows[b].tolist())
    want = {"Intersect": len(x & y), "Union": len(x | y),
            "Difference": len(x - y), "Xor": len(x ^ y)}
    assert {op: ref.count(op, a, b) for op in OPS} == want
    assert ref.count("Bitmap", a) == len(x)
    assert ref.n_loaded == sum(r.size for r in ref._rows)
    # every row is sorted and unique, which the set algebra assumes
    assert all(np.all(np.diff(r.astype(np.int64)) > 0) for r in ref._rows)


def test_every_seed_gets_the_same_work_in_another_order():
    cfg, _ = tiny()
    decks = []
    for seed in (1, 2, 3_000_000_019):
        t = Traffic(mix("count-distinct"), cfg, seed)
        ops = [t.read(i).key[0] for i in range(40)]
        decks.append(ops)
        # whole decks of 20: 10 Intersect, 5 Union, 3 Difference, 2 Xor
        for k in (0, 20):
            deck = ops[k:k + 20]
            assert [deck.count(o) for o in OPS] == [10, 5, 3, 2]
        texts = [t.read(i).text for i in range(40)]
        assert len(set(texts)) == 40
        warm = {r.text for rnd in t.warmup_rounds() for r in rnd}
        assert not warm & set(texts)
    assert decks[0] != decks[1]


def test_a_set_bit_is_read_back():
    _, ref = tiny()
    before = ref.count("Bitmap", 15)
    col = 3 * 1048576 + 12345
    assert ref.set_bit(15, col) is True
    assert ref.set_bit(15, col) is False
    assert ref.count("Bitmap", 15) == before + 1
    # through the parts a run uses: apply, then the texts that read it back
    assert ref.readback() == []
    ref.apply((17, col))
    ref.apply((15, col))
    back = ref.readback()
    assert [(r.kind, r.text, r.key) for r in back] == [
        ("read", "Count(Bitmap(frame=f, rowID=15))", ("Bitmap", 15)),
        ("read", "Count(Bitmap(frame=f, rowID=17))", ("Bitmap", 17)),
    ]
    assert ref.answer(back[0].key) == before + 1


def test_the_distinct_warm_up_covers_every_operator():
    cfg, _ = tiny()
    for seed in (1, 2, 3):
        t = Traffic(mix("count-distinct"), cfg, seed)
        rounds = t.warmup_rounds()
        first = rounds[0]
        assert len(first) == 8
        assert {r.key[0] for r in first} == set(OPS)
        assert all(r.text.startswith(f"Count({r.key[0]}(") for r in first)
        # then three rounds over the last four, which the batch cache holds
        assert len(rounds) == 4
        assert all({r.text for r in rnd} == {r.text for r in first[4:]}
                   for rnd in rounds[1:])


def test_the_repeat_mix_sends_the_texts_its_file_names():
    # Fixed panels, named with their sources: the seed makes the data and
    # never the texts, so no seed changes the work.
    cfg, _ = tiny()
    spec = mix("count-repeat")
    texts = [tuple(t) for t in spec["read"]["texts"]]
    assert len(texts) <= 4  # the batch cache's capacity
    assert all(f"texts[{i}]" in spec["sources"] for i in range(len(texts)))
    assert {op for op, _a, _b in texts} == {"Intersect"}
    for seed in (1, 2_147_483_700):
        t = Traffic(spec, cfg, seed)
        assert [t.read(i).key for i in range(len(texts))] == texts
        # client c starts c texts on, so the clients are spread over them
        assert t.read(0, 1).key == texts[1]
        assert all(r in [t.read(i) for i in range(len(texts))]
                   for rnd in t.warmup_rounds() for r in rnd)
