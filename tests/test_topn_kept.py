"""The kept stack of a direct folded TopN (``Executor._topn_kept_text``).

A direct build keeps what no text changes — the fragments' layouts, the
union, the parts, the scorer's planes and slot matrices and the
``TopStack`` — per view, slice set and options, and the next text of
that view only looks its src row up.  On CPU holders of 8 slices over
the tests' eight devices (so the scorer's groups are one a home device):

(a) for every src row, and for the plain TopN, a stack-served build and
    a fresh one give the same operands, the same ``TopStack`` and the
    exact answer;
(b) a write, the stack's expiry and a src row that one fragment does not
    hold each send the text the full way, with exact answers;
(c) a stack-served build makes no per-fragment call and takes no
    fragment lock;
(d) a quarantined device answers a stack-served text on the host, from
    the text's own src row.
"""

import types

import numpy as np
import pytest

from pilosa_tpu.cluster.topology import new_cluster
from pilosa_tpu.core.fragment import Fragment
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.device.health import COLLECTIVE, KIND_OOM, DeviceHealth
from pilosa_tpu.exec import executor as executor_mod
from pilosa_tpu.exec import plan, topn_stack
from pilosa_tpu.exec.executor import ExecOptions, Executor
from pilosa_tpu.obs import trace
from pilosa_tpu.ops import bitplane as bp
from pilosa_tpu.pql.parser import parse_string

SLICES = 8
ROWS = 10
# a dense-tier row of every slice but ABSENT, ranked by no cache (the
# frame ranks ROWS rows, and this one's count is the smallest)
UNRANKED, ABSENT = 20, 5


def _bits(seed=3):
    """``{row: set of columns}``: rows 0..9 in every slice, row 20 (two
    bits a slice) in every slice but slice 5."""
    rng = np.random.default_rng(seed)
    bits = {r: set() for r in range(ROWS)}
    bits[UNRANKED] = set()
    for s in range(SLICES):
        base = s * bp.SLICE_WIDTH
        for r in range(ROWS):
            cols = rng.choice(600, size=15 + 9 * r + int(rng.integers(0, 6)),
                              replace=False)
            bits[r].update((base + cols).tolist())
        if s != ABSENT:
            bits[UNRANKED].update([base + 3, base + 11])
    return bits


@pytest.fixture
def served(tmp_path):
    bits = _bits()
    holder = Holder(str(tmp_path))
    holder.open()
    f = holder.create_index("i").create_frame("f", cache_size=ROWS)
    rows = np.concatenate([np.full(len(c), r) for r, c in bits.items()])
    cols = np.concatenate([sorted(c) for c in bits.values()])
    f.import_bulk(rows, cols)
    c = new_cluster(1)
    ex = Executor(holder, host=c.nodes[0].host, cluster=c, tracer=trace.Tracer())
    yield ex, bits
    ex.close()
    holder.close()


def _text(row, n=100):
    if row is None:
        return f"TopN(frame=f, n={n})"
    return f"TopN(Bitmap(frame=f, rowID={row}), frame=f, n={n})"


def _exact(bits, row):
    """The reference answer: every ranked row's count (over the src where
    there is one), best first."""
    src = bits[row] if row is not None else None
    pairs = [(r, len(bits[r] if src is None else bits[r] & src)) for r in range(ROWS)]
    return sorted(((r, n) for r, n in pairs if n), key=lambda p: (-p[1], p[0]))


def _traced(ex, text):
    root = ex.tracer.start_trace("test")
    with root:
        (pairs,) = ex.execute("i", parse_string(text))
    rec = ex.tracer.finish_root(root)
    return ([(p.id, p.count) for p in pairs],
            {s["name"]: s["tags"] for s in rec["spans"]})


def _call(text):
    return plan.canonicalize_call(parse_string(text).calls[0])


def _same_operands(a: topn_stack.ScoreStack, b: topn_stack.ScoreStack):
    assert len(a.groups) == len(b.groups)
    for ga, gb in zip(a.groups, b.groups):
        assert all(x is y for x, y in zip(ga.planes, gb.planes))
        assert np.array_equal(ga.slots, gb.slots)
        assert (ga.src_slots is None) == (gb.src_slots is None)
        if ga.src_slots is not None:
            assert np.array_equal(ga.src_slots, gb.src_slots)
        assert ga.srcs is None and gb.srcs is None
    assert np.array_equal(a.base, b.base)
    assert np.array_equal(a.live_base, b.live_base)
    assert (a.size, a.rows, a.n_bytes) == (b.size, b.rows, b.n_bytes)
    assert [len(m) for m in a.members] == [len(m) for m in b.members]


def _same_stack(a: topn_stack.TopStack, b: topn_stack.TopStack):
    for name, x in vars(a).items():
        y = getattr(b, name)
        assert (x is None) == (y is None), name
        if x is not None:
            assert np.array_equal(x, y), name


# ---------------------------------------------------------------------------
# (a) a stack-served build is a fresh build
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("row", [None, *range(ROWS)])
def test_a_stack_served_build_is_the_fresh_build_of_its_text(served, row):
    ex, bits = served
    slices = list(range(SLICES))
    # a first text of the view (another src row, or the plain TopN for
    # another n) makes the kept stack
    first = None if row is None else (row + 1) % ROWS
    got, spans = _traced(ex, _text(first, n=50))
    assert got == _exact(bits, first)
    assert spans["topn.prep"]["stack"] == "made"

    c = _call(_text(row))
    served_ent, how = ex._topn_folded_entry("i", c, slices)
    assert how == "built" and served_ent["stack_way"] == "kept"
    assert served_ent["build"] == "direct"
    kept = served_ent["kept_stack"]
    assert len(served_ent["score"].groups) == (0 if row is None else SLICES)

    ex._topn_kept.clear()
    fresh = ex._topn_folded_build("i", c, slices)
    assert fresh["build"] == "direct" and fresh["stack_way"] == "made"
    _same_operands(served_ent["score"], fresh["score"])
    _same_stack(served_ent["stack"], fresh["stack"])
    assert served_ent["union"] == fresh["union"]
    assert served_ent["pins"] == fresh["pins"]
    assert [a is b for a, b in zip(kept["layouts"], fresh["kept_stack"]["layouts"])] == [
        True] * SLICES  # the fragments' layouts, as they kept them

    # and the answer of the served text is the reference's, as is the
    # unfolded per-slice protocol's for an n that trims
    assert _traced(ex, _text(row))[0] == _exact(bits, row)
    small = parse_string(_text(row, n=4)).calls[0]
    unfolded = ex._execute_topn_slices("i", small, slices, ExecOptions())
    unfolded = ex._topn_refetch("i", small, slices, ExecOptions(), 4, unfolded)
    assert _traced(ex, _text(row, n=4))[0] == [(p.id, p.count) for p in unfolded]


def test_the_src_table_finds_a_row_in_every_part_or_says_it_cannot():
    tiers = [
        (np.array([1, 4, 9]), np.array([0, 2, 1])),
        (np.array([4, 9]), np.array([7, 3])),
        (np.array([0, 4, 9, 12]), np.array([3, 0, 1, 2])),
    ]
    table = topn_stack.src_table(tiers)
    assert topn_stack.src_slots(table, 4).tolist() == [2, 7, 0]
    assert topn_stack.src_slots(table, 9).tolist() == [1, 3, 1]
    assert topn_stack.src_slots(table, 4).dtype == np.int32
    for row in (0, 1, 12, 13, -1):  # held by fewer parts, or by none
        assert topn_stack.src_slots(table, row) is None


# ---------------------------------------------------------------------------
# (b) what sends a text the full way
# ---------------------------------------------------------------------------


def test_a_write_between_two_texts_remakes_the_kept_stack(served):
    ex, bits = served
    assert _traced(ex, _text(1))[1]["topn.prep"]["stack"] == "made"
    ex.holder.frame("i", "f").set_bit("standard", 2, 7)
    bits[2].add(7)
    got, spans = _traced(ex, _text(2))
    assert spans["topn.prep"]["build"] == "direct"
    assert spans["topn.prep"]["stack"] == "made"
    assert got == _exact(bits, 2)
    assert _traced(ex, _text(3))[1]["topn.prep"]["stack"] == "kept"


def test_an_expired_kept_stack_is_remade(served, monkeypatch):
    ex, bits = served
    assert _traced(ex, _text(1))[1]["topn.prep"]["stack"] == "made"
    real = executor_mod.time

    # the executor's clock, eleven seconds on: past the rank caches'
    # re-sort throttle, which the kept stack stands in for
    later = types.SimpleNamespace(**{
        k: getattr(real, k) for k in dir(real) if not k.startswith("__")})
    later.monotonic = lambda: real.monotonic() + 11.0
    monkeypatch.setattr(executor_mod, "time", later)
    got, spans = _traced(ex, _text(2))
    assert spans["topn.prep"]["stack"] == "made"
    assert got == _exact(bits, 2)
    assert _traced(ex, _text(4))[1]["topn.prep"]["stack"] == "kept"


def test_a_src_row_one_fragment_lacks_takes_the_walked_build(served):
    ex, bits = served
    assert _traced(ex, _text(1))[1]["topn.prep"]["stack"] == "made"
    kept = next(iter(ex._topn_kept.values()))
    got, spans = _traced(ex, _text(UNRANKED))
    assert spans["topn.prep"]["build"] == "walked"
    assert "stack" not in spans["topn.prep"]
    assert got == _exact(bits, UNRANKED)
    # the kept stack stands for the texts it serves
    assert next(iter(ex._topn_kept.values())) is kept
    assert _traced(ex, _text(6))[1]["topn.prep"]["stack"] == "kept"


# ---------------------------------------------------------------------------
# (c) no per-fragment step
# ---------------------------------------------------------------------------


class _CountingLock:
    def __init__(self, lock, counts):
        self._lock, self._counts = lock, counts

    def acquire(self, *a, **kw):
        self._counts["lock"] += 1
        return self._lock.acquire(*a, **kw)

    def release(self):
        self._lock.release()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()


def test_a_stack_served_text_asks_no_fragment_anything(served, monkeypatch):
    ex, bits = served
    assert _traced(ex, _text(1))[1]["topn.prep"]["stack"] == "made"
    counts = dict.fromkeys(
        ("top_layout", "top_prepare_own_parts", "dense_rows", "_attach_dev_src",
         "_topn_versions", "_leaf_sweep", "lock"), 0)
    for cls, name in ((Fragment, "top_layout"), (Fragment, "top_prepare_own_parts"),
                      (Fragment, "dense_rows"), (Executor, "_attach_dev_src"),
                      (Executor, "_topn_versions"), (Executor, "_leaf_sweep")):
        real = getattr(cls, name)

        def spy(*a, _name=name, _real=real, **kw):
            counts[_name] += 1
            return _real(*a, **kw)

        monkeypatch.setattr(cls, name, spy)
    frags = [ex.holder.fragment("i", "f", "standard", s) for s in range(SLICES)]
    for frag in frags:
        monkeypatch.setattr(frag, "_mu", _CountingLock(frag._mu, counts))
    got, spans = _traced(ex, _text(7))
    assert spans["topn.prep"]["stack"] == "kept"
    assert spans["topn.score"]["score_cache"] == "computed"
    assert got == _exact(bits, 7)
    # prep, score and select of the whole answer
    assert counts == dict.fromkeys(counts, 0), counts


# ---------------------------------------------------------------------------
# (d) the host fallback
# ---------------------------------------------------------------------------


def test_a_quarantined_device_scores_a_stack_served_text_on_the_host(served):
    ex, bits = served
    dh = DeviceHealth(quarantine_threshold=1, open_ms=3600_000, watchdog_ms=0)
    c = new_cluster(1)
    sick = Executor(ex.holder, host=c.nodes[0].host, cluster=c, device_health=dh,
                    tracer=trace.Tracer())
    try:
        dh.failure(dh.device_paths() + [COLLECTIVE], KIND_OOM)
        assert _traced(sick, _text(1))[1]["topn.prep"]["stack"] == "made"
        for row in (3, 8, 0):
            got, spans = _traced(sick, _text(row))
            assert spans["topn.prep"]["stack"] == "kept"
            assert "hosteval" in spans and "topn.dispatch" not in spans
            # the text's own src, and not the row the states were made for
            assert got == _exact(bits, row)
            assert got == _traced(ex, _text(row))[0]
    finally:
        sick.close()
        dh.close()
