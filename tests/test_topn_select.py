"""The folded TopN's stacked selection (``pilosa_tpu/exec/topn_stack.py``).

(a) ``topn_stack.select`` over seeded random entries against the
per-part composition it replaced in ``Executor._execute_topn_folded``
(``Fragment.top_score_arrays`` + ``Fragment.select_winners`` a part,
``isin_sorted`` + ``merge_counts_by_id`` over them; copied here as it
stood): pairs and order equal with ``==``.

(b) Through the executor on CPU holders of 8 and 40 slices: every kind
of folded TopN against the unfolded per-slice protocol, ``topn.select``'s
``way``, and that a scored answer calls ``np.lexsort``, ``np.unique``
and ``np.searchsorted`` no more often at 40 slices than at 8.
"""

from dataclasses import replace

import numpy as np
import pytest

import pilosa_tpu.core.fragment as fr
from pilosa_tpu.cluster.topology import new_cluster
from pilosa_tpu.core.fragment import Fragment, SubRef, TopState
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.exec import topn_stack
from pilosa_tpu.exec.executor import ExecOptions, Executor, merge_counts_by_id
from pilosa_tpu.obs import trace
from pilosa_tpu.ops import bitplane as bp
from pilosa_tpu.pql.parser import parse_string

EMPTY = np.empty(0, np.int64)


# ---------------------------------------------------------------------------
# (a) the stacked selection against the per-part oracle
# ---------------------------------------------------------------------------


def isin_sorted(values, sorted_ref):
    if not len(sorted_ref):
        return np.zeros(len(values), dtype=bool)
    idx = np.searchsorted(sorted_ref, values)
    idx[idx == len(sorted_ref)] = len(sorted_ref) - 1
    return sorted_ref[idx] == values


def oracle(parts, score, scores, n, has_src):
    """``_execute_topn_folded``'s selection as it stood before the
    stacked one: a loop over the parts."""
    winner_ids, fulls = [], []
    for (_f, cand_ids, _own, proto, ref, _w, _s), b in zip(parts, score.base.tolist()):
        st = replace(
            proto, counts=scores[b : b + len(ref.slots)] if b >= 0 else None)
        ids, cnts, keep, short = Fragment.top_score_arrays(None, st)
        fulls.append((ids[keep], cnts[keep]))
        if not has_src:
            winner_ids.append(cand_ids[:n] if n else cand_ids)
        elif short:
            winner_ids.append(ids)
        else:
            # no pre-resolved mask: select_winners works out np.isin itself
            sel_ids, _ = Fragment.select_winners(ids, cnts, keep, cand_ids, n)
            winner_ids.append(sel_ids)
    ids2 = np.unique(np.concatenate(winner_ids)) if winner_ids else EMPTY
    if not len(ids2):
        return []
    kept = []
    for i, cts in fulls:
        m = isin_sorted(i, ids2)
        kept.append((i[m], cts[m]))
    merged = merge_counts_by_id(kept)
    if merged is None:
        return []
    uids, sums = merged
    order = np.lexsort((uids, -sums))
    if n and n < len(order):
        order = order[:n]
    return [(int(uids[k]), int(sums[k])) for k in order]


def canonical(ids, cnts):
    order = np.lexsort((ids, -cnts))
    return ids[order], cnts[order]


def random_entry(rng, *, n_parts, union_n, direct, sparse, tanimoto,
                 min_threshold, has_src=True, short=0, scores="random",
                 id_space=None):
    """A folded entry's ``parts``, its union and a score vector, as
    ``_topn_folded_build`` could have made them: a direct part lists
    the whole union as its own; a walked one lists a subset of it, of
    which a subset again is its own (the rest foreign winners).  Row
    ids are small numbers or spread over 2^40 (``id_space``)."""
    id_space = id_space or int(rng.choice([300, 2**40]))
    union = np.sort(rng.choice(id_space, size=union_n, replace=False)).astype(np.int64)
    parts = []
    for p in range(n_parts):
        if p < short and has_src:
            # nothing to intersect here: the pass short-circuited
            st = TopState(done_ids=EMPTY, done_cnts=EMPTY)
            own = rng.choice(union, size=min(3, union_n), replace=False)
            parts.append((None, np.sort(own), np.zeros(0, bool), st, None, None, None))
            continue
        k = union_n if direct else int(rng.integers(1, union_n + 1))
        listed = rng.choice(union, size=k, replace=False)
        listed, cached = canonical(listed, rng.integers(1, 50, size=k))
        if direct:
            cand_ids, own_mask = listed, None
        else:
            own_mask = rng.random(k) < 0.7
            own_mask[int(rng.integers(k))] = True
            cand_ids = listed[own_mask]
            if own_mask.all():
                own_mask = None
        if not has_src:
            st = TopState(done_ids=listed, done_cnts=cached)
            parts.append((None, cand_ids, own_mask, st, None, None, None))
            continue
        tier = rng.random(k)
        dense_pos = np.flatnonzero(tier < (0.6 if sparse else 2.0))
        sparse_pos = np.flatnonzero((tier >= 0.6) & (tier < 0.95)) if sparse else EMPTY
        st = TopState(
            cand_ids=listed,
            cand_cached=cached,
            dense_pos=dense_pos,
            sparse_pos=sparse_pos,
            sparse_cnt=rng.integers(0, 6, size=len(sparse_pos)),
            tanimoto=tanimoto,
            src_count=int(rng.integers(1, 40)) if tanimoto else 0,
            min_threshold=min_threshold,
        )
        ref = None
        if len(dense_pos):
            # widths of 8 and 16 and two plane sizes: several groups
            slots = np.zeros(-(-len(dense_pos) // 8) * 8, np.int32)
            ref = SubRef(plane=None, slots=slots, shape=(len(slots), 4),
                         plane_rows=32 * (1 + p % 2), device=None)
        parts.append((None, cand_ids, own_mask, st, ref, None, 0))
    score = topn_stack.score_stack(
        [(st, ref, w, s, f) for f, _, _, st, ref, w, s in parts])
    vector = {
        "random": lambda: rng.integers(0, 40, size=score.size),
        "ties": lambda: rng.choice([0, 5], size=score.size),
        "zeros": lambda: np.zeros(score.size),
    }[scores]().astype(np.int32)
    return parts, union, score, vector


def check(rng, n, has_src=True, **kw):
    parts, union, score, vector = random_entry(rng, has_src=has_src, **kw)
    stack = topn_stack.stack_parts(parts, union, score)
    ids, sums = topn_stack.select(stack, vector, n)
    got = list(zip(ids.tolist(), sums.tolist()))
    want = oracle(parts, score, vector, n, has_src)
    assert got == want
    return got


@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "sparse"])
@pytest.mark.parametrize("direct", [True, False], ids=["direct", "walked"])
@pytest.mark.parametrize("tanimoto", [0, 30], ids=["plain", "tanimoto"])
@pytest.mark.parametrize("min_threshold", [0, 3])
@pytest.mark.parametrize("n", [0, 1, 5, 100])
def test_the_stacked_selection_is_the_per_part_composition(
    n, min_threshold, tanimoto, direct, sparse
):
    rng = np.random.default_rng(
        [n, min_threshold, tanimoto, int(direct), int(sparse)])
    answered = 0
    for _ in range(4):
        answered += bool(check(
            rng, n, n_parts=int(rng.integers(2, 24)), union_n=int(rng.integers(2, 40)),
            direct=direct, sparse=sparse, tanimoto=tanimoto,
            min_threshold=min_threshold))
    assert answered  # the cases are not all empty answers


@pytest.mark.parametrize("n", [0, 1, 5, 100])
@pytest.mark.parametrize("direct", [True, False], ids=["direct", "walked"])
def test_a_short_circuited_part_among_scored_ones(n, direct):
    rng = np.random.default_rng([7, n, int(direct)])
    assert check(rng, n, n_parts=9, union_n=20, direct=direct, sparse=True,
                 tanimoto=0, min_threshold=0, short=3)


@pytest.mark.parametrize("n", [0, 1, 5, 100])
@pytest.mark.parametrize("direct", [True, False], ids=["direct", "walked"])
def test_no_src_every_part_final(n, direct):
    rng = np.random.default_rng([8, n, int(direct)])
    got = check(rng, n, has_src=False, n_parts=11, union_n=30, direct=direct,
                sparse=False, tanimoto=0, min_threshold=0)
    assert got and (not n or len(got) <= n)


@pytest.mark.parametrize("n", [0, 1, 5, 100])
@pytest.mark.parametrize("direct", [True, False], ids=["direct", "walked"])
def test_equal_counts_rank_by_id(n, direct):
    rng = np.random.default_rng([9, n, int(direct)])
    got = check(rng, n, n_parts=12, union_n=25, direct=direct, sparse=False,
                tanimoto=0, min_threshold=0, scores="ties")
    assert got == sorted(got, key=lambda p: (-p[1], p[0]))


@pytest.mark.parametrize("n", [0, 5])
@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "sparse"])
def test_all_zero_scores(n, sparse):
    rng = np.random.default_rng([10, n, int(sparse)])
    got = check(rng, n, n_parts=6, union_n=12, direct=True, sparse=sparse,
                tanimoto=0, min_threshold=0, scores="zeros")
    assert sparse or got == []  # only probed sparse counts can be above 0


@pytest.mark.parametrize("n", [0, 1, 5, 100])
@pytest.mark.parametrize("direct", [True, False], ids=["direct", "walked"])
def test_one_part(n, direct):
    rng = np.random.default_rng([11, n, int(direct)])
    assert check(rng, n, n_parts=1, union_n=16, direct=direct, sparse=True,
                 tanimoto=0, min_threshold=0)


def test_an_entrys_arrays_are_read_only():
    """Every query of an entry reads them, concurrently."""
    rng = np.random.default_rng(13)
    parts, union, score, vector = random_entry(
        rng, n_parts=5, union_n=9, direct=False, sparse=True, tanimoto=30,
        min_threshold=0)
    stack = topn_stack.stack_parts(parts, union, score)
    before = topn_stack.select(stack, vector, 2)
    for name, arr in vars(stack).items():
        assert arr is not None, name  # this entry has every array
        with pytest.raises(ValueError, match="read-only"):
            arr[...] = 0
    after = topn_stack.select(stack, vector, 2)
    assert [a.tolist() for a in before] == [a.tolist() for a in after]


def test_a_deployment_sized_entry_trims_where_n_is_under_a_row():
    """954 parts of 64, n = 10: every row trims (the row-wise n-th
    key), and the answer is the loop's."""
    rng = np.random.default_rng(12)
    got = check(rng, 10, n_parts=954, union_n=64, direct=True, sparse=False,
                tanimoto=0, min_threshold=0)
    assert len(got) == 10


# ---------------------------------------------------------------------------
# (b) through the executor
# ---------------------------------------------------------------------------

ROWS = 10


def _holder(tmp_path_factory, slices):
    """``slices`` fragments of frames ``f`` and ``o`` (rows 0..9, all
    dense tier, every row in every slice), ``g`` (the same, and row 50
    in slice 1 alone: a foreign winner elsewhere) and ``s`` (the same
    rows under a dense budget of 6: rows 6..9 in the sparse tier)."""
    holder = Holder(str(tmp_path_factory.mktemp(f"sel{slices}")))
    holder.open()
    idx = holder.create_index("i")
    rng = np.random.default_rng(slices)
    rows, cols = [], []
    for s in range(slices):
        for r in range(ROWS):
            c = rng.choice(600, size=15 + 9 * r + int(rng.integers(0, 6)), replace=False)
            rows.append(np.full(len(c), r))
            cols.append(c + s * bp.SLICE_WIDTH)
    rows, cols = np.concatenate(rows), np.concatenate(cols)
    for name in ("f", "o", "g"):
        idx.create_frame(name).import_bulk(rows, cols)
    idx.frame("g").import_bulk(
        np.full(100, 50), bp.SLICE_WIDTH + np.arange(0, 300, 3))
    orig = fr.Fragment.__init__

    def budget_of_six(self, *a, **kw):
        kw.setdefault("dense_row_budget", 6)
        orig(self, *a, **kw)

    fr.Fragment.__init__ = budget_of_six
    try:
        idx.create_frame("s").import_bulk(rows, cols)
    finally:
        fr.Fragment.__init__ = orig
    assert len(holder.fragment("i", "s", "standard", 0)._sparse) == ROWS - 6
    return holder


@pytest.fixture(scope="module", params=[8, 40])
def served(request, tmp_path_factory):
    slices = request.param
    holder = _holder(tmp_path_factory, slices)
    c = new_cluster(1)
    ex = Executor(holder, host=c.nodes[0].host, cluster=c, tracer=trace.Tracer())
    yield ex, slices
    ex.close()
    holder.close()


KINDS = {
    "src": ("TopN(Bitmap(frame=f, rowID=3), frame=f, n=4)", "direct"),
    "src_n_covers": ("TopN(Bitmap(frame=f, rowID=3), frame=f, n=100)", "direct"),
    "plain": ("TopN(frame=f, n=4)", "direct"),
    "plain_foreign": ("TopN(frame=g, n=3)", "walked"),
    "ids": ("TopN(Bitmap(frame=f, rowID=3), frame=f, ids=[1, 3, 5, 6])", None),
    "threshold": ("TopN(Bitmap(frame=f, rowID=2), frame=f, n=5, threshold=9)", "walked"),
    "tanimoto": (
        "TopN(Bitmap(frame=f, rowID=7), frame=f, n=4, tanimotoThreshold=20)", "walked"),
    "src_second_frame": ("TopN(Bitmap(frame=o, rowID=3), frame=f, n=6)", "walked"),
    "foreign_winner": ("TopN(Bitmap(frame=g, rowID=3), frame=g, n=3)", "walked"),
    "sparse_tier": ("TopN(Bitmap(frame=s, rowID=3), frame=s, n=7)", "walked"),
}


def _traced(ex, pql):
    root = ex.tracer.start_trace("test")
    with root:
        (pairs,) = ex.execute("i", parse_string(pql))
    rec = ex.tracer.finish_root(root)
    return ([(p.id, p.count) for p in pairs],
            {s["name"]: s["tags"] for s in rec["spans"]})


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_every_kind_of_folded_topn_answers_as_the_unfolded_path(served, kind):
    ex, slices = served
    pql, build = KINDS[kind]
    got, spans = _traced(ex, pql)
    assert got, pql
    call = parse_string(pql).calls[0]
    unfolded = ex._execute_topn_slices("i", call, list(range(slices)), ExecOptions())
    if build is not None:
        # the reference's second round: exact counts for the winners
        unfolded = ex._topn_refetch(
            "i", call, list(range(slices)), ExecOptions(), call.args.get("n", 0),
            unfolded)
    assert got == [(p.id, p.count) for p in unfolded]
    if build is None:
        assert "topn.select" not in spans  # never folded
        return
    assert spans["topn.prep"]["build"] == build
    assert spans["topn.select"] == {"parts": slices, "way": "stacked"}
    if kind != "plain" and kind != "plain_foreign":
        assert spans["topn.score"]["score_cache"] == "computed"


COUNTED = ("lexsort", "unique", "searchsorted")


def _count_calls(monkeypatch, fn):
    calls = dict.fromkeys(COUNTED, 0)
    with monkeypatch.context() as m:
        for name in COUNTED:
            real = getattr(np, name)

            def spy(*a, _name=name, _real=real, **kw):
                calls[_name] += 1
                return _real(*a, **kw)

            m.setattr(np, name, spy)
        out = fn()
    return out, calls


# what a scored answer may call, whatever the slice count
CALLS_AN_ANSWER = {"lexsort": 1, "unique": 1, "searchsorted": 1}
_seen: dict = {}


def test_a_scored_answers_numpy_calls_do_not_grow_with_the_slices(
    served, monkeypatch
):
    """A second distinct text on an unchanged index (the fragments keep
    their layouts): prep, score and select of the whole answer."""
    ex, slices = served
    _traced(ex, "TopN(Bitmap(frame=f, rowID=0), frame=f, n=4)")
    (got, spans), calls = _count_calls(
        monkeypatch,
        lambda: _traced(ex, "TopN(Bitmap(frame=f, rowID=1), frame=f, n=4)"))
    assert got and spans["topn.prep"]["build"] == "direct"
    assert spans["topn.score"]["score_cache"] == "computed"
    assert all(calls[k] <= CALLS_AN_ANSWER[k] for k in COUNTED), calls
    assert _seen.setdefault("direct", calls) == calls  # 8 slices and 40 alike


@pytest.mark.parametrize("kind", ["tanimoto", "src_second_frame", "sparse_tier"])
def test_rescoring_a_walked_entry_calls_numpy_no_more_at_forty_slices(
    served, monkeypatch, kind
):
    """Score and select alone (the entry is a hit, its score memo
    dropped), for builds that pad."""
    ex, slices = served
    pql = KINDS[kind][0]
    want, _ = _traced(ex, pql)
    ent = next(e for k, e in ex._topn_cache.items()
               if k[1] == str(parse_string(pql).calls[0]))
    ent.pop("scores"), ent.pop("score_event")
    (got, spans), calls = _count_calls(monkeypatch, lambda: _traced(ex, pql))
    assert got == want
    assert spans["topn.prep"]["prep_cache"] == "hit"
    assert spans["topn.score"]["score_cache"] == "computed"
    assert calls == {"lexsort": 1, "unique": 0, "searchsorted": 0}
    assert _seen.setdefault(kind, calls) == calls


def test_a_scored_answer_takes_the_pools_lock_a_fixed_number_of_times(
    served, monkeypatch
):
    """The residency pool has ONE lock: a touch a fragment from every
    request thread is a convoy on it.  A build keeps the mirrors it
    reads recent under one hold (``touch_many``), the scoring pins them
    under one more."""
    from pilosa_tpu import device as device_mod

    ex, slices = served
    _traced(ex, "TopN(Bitmap(frame=f, rowID=4), frame=f, n=4)")
    pool = device_mod.pool()
    calls = dict.fromkeys(("touch", "touch_many", "pin_many", "pin"), 0)
    touched = []
    for name in calls:
        real = getattr(pool, name)

        def spy(*a, _name=name, _real=real, **kw):
            calls[_name] += 1
            if _name == "touch_many":
                touched.append(len(list(a[0])))
            return _real(*a, **kw)

        monkeypatch.setattr(pool, name, spy)
    got, spans = _traced(ex, "TopN(Bitmap(frame=f, rowID=5), frame=f, n=4)")
    assert got and spans["topn.prep"]["build"] == "direct"
    assert spans["topn.score"]["score_cache"] == "computed"
    assert calls == {"touch": 0, "touch_many": 1, "pin_many": 1, "pin": 0}, calls
    assert touched == [slices]  # every mirror the parts read
