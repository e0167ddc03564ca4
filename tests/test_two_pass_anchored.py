"""The anchored count's two passes (PR 27): the metadata pass settles
the route from what the views and fragments already hold — whether a
view has a sparse-tier row at all, then cached cardinalities and
container formats — and only a route that answers reads an anchor's
positions or a leaf's payload.  A decline scans nothing.

Every answer is held to a numpy set oracle; the ``anchored.prepass``
span's tags (``outcome``, ``slices_walked``, ``anchors_scanned``) are
the mechanism's engagement counter and are asserted beside it.
"""

import pytest

import pilosa_tpu.core.fragment as fr
from pilosa_tpu.cluster.topology import new_cluster
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.exec import Executor, plan
from pilosa_tpu.exec.executor import FrameNotFoundError
from pilosa_tpu.obs import trace
from pilosa_tpu.ops import bitplane as bp
from pilosa_tpu.pql.parser import parse_string

SW = bp.SLICE_WIDTH
NO_SCANS = {"row_positions": 0, "host_payload": 0, "np_row_to_columns": 0}

OPS = {
    "Intersect": lambda a, b: a & b,
    "Difference": lambda a, b: a - b,
}


@pytest.fixture
def holder(tmp_path):
    h = Holder(str(tmp_path / "data"))
    h.open()
    yield h
    h.close()


def _zero_dense_budget(monkeypatch):
    """Fragments made from here on put every row in the sparse tier,
    where the compressed container formats — and so the anchored route
    — engage."""
    orig = fr.Fragment.__init__

    def zero_budget(self, *a, **kw):
        kw.setdefault("dense_row_budget", 0)
        orig(self, *a, **kw)

    monkeypatch.setattr(fr.Fragment, "__init__", zero_budget)


@pytest.fixture
def sparse_tier(monkeypatch):
    _zero_dense_budget(monkeypatch)


@pytest.fixture
def scans(monkeypatch):
    """Count the calls that read a plane or build a payload, and the
    per-row metadata reads of the walk."""
    calls = dict(NO_SCANS, row_meta=0)

    def spy(owner, name):
        orig = getattr(owner, name)

        def wrapped(*a, **kw):
            calls[name] += 1
            return orig(*a, **kw)

        monkeypatch.setattr(owner, name, wrapped)

    spy(fr.Fragment, "row_positions")
    spy(fr.Fragment, "host_payload")
    spy(fr.Fragment, "row_meta")
    spy(bp, "np_row_to_columns")
    return calls


def _executor(holder):
    c = new_cluster(1)
    return Executor(
        holder, host=c.nodes[0].host, cluster=c, tracer=trace.Tracer()
    )


def _load(holder, rows, frame="f"):
    """rows: {row id: set of column ids}."""
    idx = holder.create_index_if_not_exists("i")
    f = idx.create_frame_if_not_exists(frame)
    rows_in, cols_in = [], []
    for r, cols in rows.items():
        rows_in += [r] * len(cols)
        cols_in += sorted(cols)
    f.import_bulk(rows_in, cols_in)
    return f


def _count(ex, pql):
    """Run one Count under a trace: (answer, its anchored.prepass tags)."""
    tr = ex.tracer
    root = tr.start_trace("query")
    token = root.activate()
    try:
        (got,) = ex.execute("i", parse_string(pql), None, None)
    finally:
        root.deactivate(token)
        rec = tr.finish_root(root)
    pre = [s for s in rec["spans"] if s["name"] == "anchored.prepass"]
    assert len(pre) == 1
    # ``blocked`` (an answered pre-pass fetches its count itself: time
    # blocked on purpose, kind ``device``) is no tag of the work
    tags = dict(pre[0]["tags"])
    assert tags.pop("blocked", {"device": 0}).keys() == {"device"}
    return int(got), tags


def _pql(op, a=1, b=2, frame_a="f", frame_b="f"):
    return (
        f"Count({op}(Bitmap(rowID={a}, frame={frame_a}),"
        f" Bitmap(rowID={b}, frame={frame_b})))"
    )


def _scattered(rng, card, slice_i=0):
    return {
        int(p) + slice_i * SW for p in rng.choice(SW, size=card, replace=False)
    }


# ---------------------------------------------------------------------------
# (a) a dense-tier corpus declines without a scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("op", sorted(OPS))
def test_dense_tier_decline_scans_nothing(holder, rng, scans, op):
    slices = 40
    rows = {1: set(), 2: set()}
    for s in range(slices):
        shared = _scattered(rng, 6, s)
        rows[1] |= shared | _scattered(rng, 5, s)
        rows[2] |= shared | _scattered(rng, 7, s)
    _load(holder, rows)
    ex = _executor(holder)
    got, tags = _count(ex, _pql(op))
    assert got == len(OPS[op](rows[1], rows[2]))
    # no view holds a sparse-tier row: decided before any slice is walked
    assert tags == {
        "outcome": "declined_dense",
        "slices_walked": 0,
        "anchors_scanned": 0,
    }
    assert scans == dict(NO_SCANS, row_meta=0)


# ---------------------------------------------------------------------------
# (b) too dense at the last slice: nothing scanned on the way there
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("op", sorted(OPS))
def test_too_dense_at_the_last_slice_scans_nothing(
    sparse_tier, holder, rng, scans, op
):
    too_many = Executor.ANCHORED_MAX_POSITIONS + 200
    rows = {1: set(), 2: set()}
    for s in range(2):  # compressed, anchorable slices first
        shared = _scattered(rng, 40, s)
        rows[1] |= shared | _scattered(rng, 300, s)
        rows[2] |= shared | _scattered(rng, 500, s)
    rows[1] |= _scattered(rng, too_many, 2)
    rows[2] |= _scattered(rng, too_many + 50, 2)
    _load(holder, rows)
    ex = _executor(holder)
    plan.clear_program_caches()
    got, tags = _count(ex, _pql(op))
    assert got == len(OPS[op](rows[1], rows[2]))  # the batched path's answer
    assert tags == {
        "outcome": "declined_too_dense",
        "slices_walked": 3,
        "anchors_scanned": 0,
    }
    assert scans == dict(NO_SCANS, row_meta=2 * 3)
    assert plan.program_cache_stats().get("plan.anchored", 0) == 0


# ---------------------------------------------------------------------------
# (c) an answered count scans exactly the slices whose anchor is non-empty
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("op", sorted(OPS))
def test_answered_count_scans_the_nonempty_anchors(
    sparse_tier, holder, rng, scans, op
):
    slices = 4
    rows = {1: set(), 2: set()}
    for s in range(slices):
        shared = _scattered(rng, 30, s)
        if s != 2:  # slice 2: row 1 (an anchor of either operator) is empty
            rows[1] |= shared | _scattered(rng, 200, s)
        rows[2] |= shared | _scattered(rng, 900, s)
    _load(holder, rows)
    ex = _executor(holder)
    plan.clear_program_caches()
    got, tags = _count(ex, _pql(op))
    assert got == len(OPS[op](rows[1], rows[2]))
    assert tags == {
        "outcome": "answered",
        "slices_walked": slices,
        "anchors_scanned": slices - 1,
    }
    assert scans["row_positions"] == slices - 1
    assert scans["host_payload"] == 2 * (slices - 1)
    assert scans["row_meta"] == 2 * slices
    assert plan.program_cache_stats().get("plan.anchored", 0) > 0


def test_leaves_of_two_frames_resolve_their_own_views(
    sparse_tier, holder, rng
):
    a = _scattered(rng, 400) | _scattered(rng, 300, 1)
    b = set(sorted(a)[::3]) | _scattered(rng, 600)
    _load(holder, {1: a}, frame="f")
    _load(holder, {2: b}, frame="g")
    ex = _executor(holder)
    got, tags = _count(ex, _pql("Intersect", frame_b="g"))
    assert got == len(a & b)
    assert tags["outcome"] == "answered" and tags["anchors_scanned"] == 2
    # an absent frame is the main path's error to raise, not the pre-pass's
    with pytest.raises(FrameNotFoundError):
        _count(ex, _pql("Intersect", frame_b="nope"))


def test_the_route_follows_the_tiers_it_finds(holder, rng, monkeypatch, scans):
    """One algorithm, adapting to what it observes: a dense-tier view
    declines without a walk; once a fragment with sparse-tier rows
    joins the same view (a write moves the epoch, so the view is asked
    again) the same text walks, and answers on the anchored route."""
    rows = {1: _scattered(rng, 300), 2: _scattered(rng, 500)}
    f = _load(holder, rows)
    view = f.view("standard")
    ex = _executor(holder)
    pql = _pql("Intersect")
    got, tags = _count(ex, pql)
    assert got == len(rows[1] & rows[2])
    assert (tags["outcome"], tags["slices_walked"]) == ("declined_dense", 0)
    assert view.dense_tier_only()

    _zero_dense_budget(monkeypatch)
    more = {1: _scattered(rng, 200, 1), 2: _scattered(rng, 400, 1)}
    more[2] |= set(sorted(more[1])[::2])
    _load(holder, more)  # slice 1: a new fragment, every row sparse-tier
    assert not view.dense_tier_only()
    plan.clear_program_caches()
    got, tags = _count(ex, pql)
    assert got == len(rows[1] & rows[2]) + len(more[1] & more[2])
    assert tags == {
        "outcome": "answered", "slices_walked": 2, "anchors_scanned": 2
    }
    assert plan.program_cache_stats().get("plan.anchored", 0) > 0


class _Hydrator:
    """The tier manager as View sees it: touch hot, hydrate cold."""

    def __init__(self, frag):
        self.frag, self.touched, self.hydrated = frag, [], []

    def touch(self, view, slice_i):
        self.touched.append(slice_i)

    def hydrate(self, view, slice_i):
        self.hydrated.append(slice_i)
        view.adopt_hydrated(slice_i, self.frag)
        return self.frag


def test_fragments_at_is_fragment_per_slice(holder, rng, tmp_path):
    f = _load(holder, {1: _scattered(rng, 9) | _scattered(rng, 9, 2)})
    view = f.view("standard")
    slices = [0, 1, 2, 3]
    assert view.fragments_at(slices) == [view.fragment(s) for s in slices]
    assert view.fragments_at(slices)[1] is None and view.dense_tier_only()
    # a cold slice hydrates through the hydrator, a hot one is touched;
    # until it has hydrated nobody knows its tiers
    cold = fr.Fragment(str(tmp_path / "cold"), "i", "f", "standard", 3)
    cold.open()
    try:
        view.hydrator = hyd = _Hydrator(cold)
        assert view.register_cold(3, object())
        cold.set_bit(1, 3 * SW + 5)  # any write retires the memo
        assert not view.dense_tier_only()
        got = view.fragments_at(slices)
        assert got == [view.fragment(0), None, view.fragment(2), cold]
        assert hyd.hydrated == [3] and hyd.touched[:2] == [0, 2]
        cold.set_bit(1, 3 * SW + 6)
        assert view.dense_tier_only()  # hydrated: its rows are dense-tier
    finally:
        view.hydrator = None
        view.remove_fragment(3)


# ---------------------------------------------------------------------------
# (d) Fragment.row_meta is host_payload without the payload
# ---------------------------------------------------------------------------


def _clustered(start, n):
    return set(range(start, start + n))


# kind -> (dense budget, the row's first bits, the write, formats before/after)
META_CASES = {
    "dense_tier": (None, lambda rng: _scattered(rng, 50),
                   lambda rng: {7}, bp.FMT_DENSE, bp.FMT_DENSE),
    "sparse": (0, lambda rng: _scattered(rng, 150),
               lambda rng: _scattered(rng, 17_000), bp.FMT_SPARSE, bp.FMT_DENSE),
    "rle": (0, lambda rng: _clustered(1000, 2000),
            lambda rng: _scattered(rng, 3000), bp.FMT_RLE, bp.FMT_SPARSE),
    "absent": (0, lambda rng: set(),
               lambda rng: {11, 12}, None, bp.FMT_SPARSE),
}


@pytest.mark.parametrize("kind", sorted(META_CASES))
def test_row_meta_agrees_with_host_payload(tmp_path, rng, scans, kind):
    budget, first, write, fmt_before, fmt_after = META_CASES[kind]
    kw = {} if budget is None else {"dense_row_budget": budget}
    frag = fr.Fragment(str(tmp_path / "frag"), "i", "f", "standard", 0, **kw)
    frag.open()
    try:
        frag.set_bit(3, 1)  # another row, so the fragment is never empty
        for stage, bits, want_fmt in (
            ("before", first(rng), fmt_before),
            ("after", write(rng), fmt_after),
        ):
            if bits:
                cols = sorted(bits)
                frag.import_bulk([5] * len(cols), cols)
            meta = frag.row_meta(5)
            assert {k: scans[k] for k in NO_SCANS} == NO_SCANS, stage
            hp = frag.host_payload(5)
            scans["host_payload"] = 0
            if hp is None:
                assert meta == (0, None), stage
            else:
                assert meta == (hp[3], hp[0]), stage
            assert meta[1] == want_fmt, stage
            assert frag.holds_sparse_tier_rows() == (budget == 0), stage
    finally:
        frag.close()
