"""A BSI aggregate computed from the resident plane mirrors in place
(``bp.aggregate_planes``) against the two ways that stood before it:
the assembled leaf batch and ``hosteval``.  The three give the same
per-slice partial vectors' decode, bit for bit, over every comparison
operator, negative ranges, ``><``, with and without a filter, for
``Sum`` / ``Min`` / ``Max``, on one device and on the eight virtual
ones; a sparse-tier plane and cold mirrors take the leaf batch.  What
the fragments hold, and in which slots, is data: it makes no program."""

import numpy as np
import pytest

from pilosa_tpu.core import fragment as fragment_mod
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.exec import plan
from pilosa_tpu.exec.executor import Executor
from pilosa_tpu.obs import trace
from pilosa_tpu.ops import bitplane as bp
from pilosa_tpu.pql.parser import parse_string

SLICES = 3
PER_SLICE = 400


def _table(path):
    """A holder with a signed field ``q``, a small signed field ``d``
    and a plain frame ``f`` of three rows over ``SLICES`` slices, a
    frame ``g`` whose one row only the first slice holds, and the same
    table as plain columns."""
    holder = Holder(str(path))
    holder.open()
    idx = holder.create_index("i")
    f = idx.create_frame("f")
    v = idx.create_frame("v")
    v.set_options(range_enabled=True)
    v.create_field("q", -100, 100)
    v.create_field("d", -9, 9)
    rng = np.random.default_rng(34)
    cols, q, d, rows = [], [], [], []
    for s in range(SLICES):
        c = s * bp.SLICE_WIDTH + np.unique(rng.integers(0, 9000, PER_SLICE))
        cols.append(c)
        q.append(rng.integers(-100, 101, c.size))
        d.append(rng.integers(-9, 10, c.size))
        rows.append(rng.integers(1, 4, c.size))
        v.import_value("q", c, q[-1])
        v.import_value("d", c, d[-1])
        f.import_bulk(rows[-1], c)
    idx.create_frame("g").import_bulk(np.ones(50, dtype=np.int64), cols[0][:50])
    plain = {k: np.concatenate(x) for k, x in
             (("q", q), ("d", d), ("row", rows), ("col", cols))}
    plain["g"] = np.isin(plain["col"], cols[0][:50])
    _upload_mirrors(holder)
    return holder, plain


def _upload_mirrors(holder):
    """Every fragment's mirror on its home device, as a served index
    has them once the prefetcher has run: cold ones take the batch."""
    for idx in holder.indexes().values():
        for frame in idx.frames().values():
            for view in frame.views().values():
                for frag in view.fragments():
                    frag.device_plane()


@pytest.fixture(scope="module")
def table(tmp_path_factory):
    """The table of the tests that hold every slice on one device."""
    bp.configure_mesh_devices(1)  # as ``one_chip``: the mirrors upload here
    try:
        holder, plain = _table(tmp_path_factory.mktemp("bsi"))
    finally:
        bp.configure_mesh_devices(0)
    yield holder, plain
    holder.close()


@pytest.fixture(scope="module")
def table_on_eight(tmp_path_factory):
    """The same table for the tests that leave every slice on its own
    home device: a mirror stays on the device it was uploaded to."""
    holder, plain = _table(tmp_path_factory.mktemp("bsi8"))
    yield holder, plain
    holder.close()


def traced(ex: Executor, text: str, slices=None):
    """``text``'s answer and the spans of its trace, by name."""
    root = ex.tracer.start_trace("query")
    token = root.activate()
    try:
        (res,) = ex.execute("i", parse_string(text), slices=slices)
    finally:
        root.deactivate(token)
        rec = ex.tracer.finish_root(root)
    return res, {s["name"]: s for s in rec["spans"]}


def want(name: str, values: np.ndarray):
    """The plain answer: ``(value, count)`` as a ValCount holds them."""
    if name == "Sum":
        return (int(values.sum()), int(values.size))
    if not values.size:
        return None
    best = values.min() if name == "Min" else values.max()
    return (int(best), int((values == best).sum()))


def three_ways(ex: Executor, text: str):
    """``text``'s answer in place, through the leaf batch and from
    ``hosteval``, each as ``(value, count)`` or None, and the way the
    executor itself took."""
    c = parse_string(text).calls[0]
    slices = list(range(SLICES))
    taken = []
    prep = ex._agg_in_place_prep

    def spy(*a):
        taken.append(prep(*a))
        return taken[-1]

    ex._agg_in_place_prep = spy
    try:
        own = ex._bsi_agg_slices("i", c, slices)
        ex._agg_in_place_prep = lambda *a: "asked"
        batch = ex._bsi_agg_slices("i", c, slices)
    finally:
        del ex._agg_in_place_prep
    rc = ex._rewrite_bsi_agg("i", c)
    host = ex._decode_agg_parts(
        c, int(rc.args["nplanes"]), ex.hosteval.agg_partials("i", rc, slices).values())
    way = taken[0] if isinstance(taken[0], str) else "in_place"
    pair = lambda r: None if r is None else (r.value, r.count)  # noqa: E731
    return pair(own), pair(batch), pair(host), way


OPS = {"<": np.less, "<=": np.less_equal, "==": np.equal, "!=": np.not_equal,
       ">=": np.greater_equal, ">": np.greater}

# (aggregate, filter text or None, mask over the plain table)
CASES = [
    ("Sum", f"Range(frame=v, d {op} {k})", lambda t, op=op, k=k: OPS[op](t["d"], k))
    for op, k in (("<", -2), ("<=", 4), ("==", -7), ("!=", 0), (">=", -9), (">", 3))
] + [
    ("Sum", "Range(frame=v, d >< [-5, 3])", lambda t: (t["d"] >= -5) & (t["d"] <= 3)),
    ("Sum", "Range(frame=v, q >< [-100, -40])", lambda t: (t["q"] >= -100) & (t["q"] <= -40)),
    ("Sum", None, lambda t: np.ones(t["q"].size, bool)),
    ("Min", None, lambda t: np.ones(t["q"].size, bool)),
    ("Max", None, lambda t: np.ones(t["q"].size, bool)),
    ("Min", "Range(frame=v, d > 6)", lambda t: t["d"] > 6),
    ("Max", "Bitmap(frame=f, rowID=3)", lambda t: t["row"] == 3),
    ("Sum", "Intersect(Bitmap(frame=f, rowID=2), Range(frame=v, d >< [-3, 8]), "
            "Range(frame=v, q < 55))",
     lambda t: (t["row"] == 2) & (t["d"] >= -3) & (t["d"] <= 8) & (t["q"] < 55)),
    ("Max", "Difference(Bitmap(frame=f, rowID=1), Range(frame=v, q >= 90))",
     lambda t: (t["row"] == 1) & ~(t["q"] >= 90)),
    ("Sum", "Range(frame=v, d > 9)", lambda t: t["d"] > 9),  # clamped: nothing
    ("Min", "Bitmap(frame=f, rowID=77)", lambda t: t["row"] == 77),  # a row held nowhere
    # a view two of the three slices hold nothing of: they ride with a stand-in
    ("Sum", "Union(Bitmap(frame=g, rowID=1), Range(frame=v, d == 9))",
     lambda t: t["g"] | (t["d"] == 9)),
]


def _text(name, filt):
    return f"{name}({filt + ', ' if filt else ''}frame=v, field=q)"


@pytest.mark.parametrize("name,filt,mask", CASES,
                         ids=[f"{n}-{f or 'all'}" for n, f, _ in CASES])
def test_in_place_is_the_leaf_batch_is_hosteval_on_one_device(
    one_chip, table, name, filt, mask
):
    holder, t = table
    ex = Executor(holder)
    try:
        own, batch, host, way = three_ways(ex, _text(name, filt))
    finally:
        ex.close()
    assert way == "in_place"
    expected = want(name, t["q"][mask(t)])
    # a Sum over nothing is None here and {0, 0} at the call's end
    assert own == batch == host == (expected if expected != (0, 0) else None)


@pytest.mark.parametrize("name,filt,mask", [CASES[6], CASES[9], CASES[13]],
                         ids=["Sum-between", "Min-all", "Sum-tree"])
def test_in_place_is_the_leaf_batch_is_hosteval_on_eight_devices(
    table_on_eight, name, filt, mask
):
    """Every slice on its own home device: a launch a device, the
    vectors fetched together."""
    holder, t = table_on_eight
    assert len({bp.home_device(s) for s in range(SLICES)}) == SLICES
    ex = Executor(holder)
    try:
        own, batch, host, way = three_ways(ex, _text(name, filt))
        (res,) = ex.execute("i", parse_string(_text(name, filt)))
    finally:
        ex.close()
    assert way == "in_place"
    assert own == batch == host == want(name, t["q"][mask(t)]) == (res.value, res.count)


def test_texts_that_differ_in_a_constant_run_one_program(one_chip, table):
    holder, t = table
    ex = Executor(holder)
    try:
        plan.clear_program_caches()
        seen = []
        for lo, hi, k in ((-5, 3, 10), (0, 9, -100), (-9, -9, 0)):
            text = (f"Sum(Intersect(Range(frame=v, d >< [{lo}, {hi}]), "
                    f"Range(frame=v, q > {k})), frame=v, field=q)")
            (res,) = ex.execute("i", parse_string(text))
            m = (t["d"] >= lo) & (t["d"] <= hi) & (t["q"] > k)
            assert (res.value, res.count) == want("Sum", t["q"][m])
            seen.append(plan.program_cache_stats()["bitplane.aggregatePlanes"])
        assert seen == [1, 1, 1]
        assert plan.program_cache_compile_ms()["bsi.agg"] > 0
        assert seen[-1] <= plan.program_cache_bounds()["bitplane.aggregatePlanes"]
    finally:
        ex.close()


def test_the_spans_of_an_in_place_sum_do_not_grow_with_the_slices(one_chip, table):
    holder, _ = table
    ex = Executor(holder, tracer=trace.Tracer())
    try:
        text = "Sum(Range(frame=v, d >< [-5, 3]), frame=v, field=q)"
        counts = []
        for n in (1, SLICES, 1, SLICES):  # each shape's first call compiles
            _, spans = traced(ex, text, list(range(n)))
            counts.append(len(spans))
            agg = spans["bsi.agg"]["tags"]
            assert agg["way"] == "in_place" and agg["slices"] == n
            assert agg["launches"] == 1 and agg["planes"] > 0
            assert agg["bytes"] == agg["planes"] * bp.WORDS_PER_SLICE * 4
            assert {"bsi.prep", "bsi.dispatch", "bsi.fetch", "bsi.decode"} <= set(spans)
            assert "plan" not in spans and "coalesce" not in spans
        assert counts[2] == counts[3] <= 12 and counts[0] == counts[2] + 1
    finally:
        ex.close()


def test_prewarm_warms_the_plain_sum_of_the_holders_own_fields(one_chip, table):
    """The shapes are the executor's own layout of a plain Sum at the
    row class of the holder's planes (no mirror is uploaded for them),
    and the Sum that follows compiles nothing."""
    from pilosa_tpu.exec import warmup

    holder, t = table
    shapes = warmup.agg_shapes(holder)
    members = bp.pow2_bucket(SLICES)
    # d (depth 4) and q (depth 7) share the depth-8 bucket, so one
    # expression and one layout: exists, sign, 8 magnitude leaves of which
    # the pads are zeros; d's planes have 8 rows, q's 16
    assert sorted((len(e) - 2, [c[0] for c in cols].count("row"), units, shape[0], m)
                  for e, cols, units, shape, m in shapes) == [
        (10, 6, ("whole",), 8, members), (10, 9, ("whole",), 16, members)]
    plan.clear_program_caches()
    assert warmup.prewarm_agg(shapes) == 2
    assert plan.program_cache_stats()["bitplane.aggregatePlanes"] == 2
    assert plan.program_cache_compile_ms()["bsi.agg"] > 0
    ex = Executor(holder, tracer=trace.Tracer())
    try:
        for fld in ("q", "d"):
            res, spans = traced(ex, f"Sum(frame=v, field={fld})")
            assert (res.value, res.count) == want("Sum", t[fld])
            assert spans["bsi.agg"]["tags"]["way"] == "in_place"
            assert "compile" not in spans
    finally:
        ex.close()
    stats, bounds = plan.program_cache_stats(), plan.program_cache_bounds()
    assert stats["bitplane.aggregatePlanes"] == 2 <= bounds["bitplane.aggregatePlanes"]


def test_what_the_fragments_hold_and_where_is_data_and_makes_no_program(
    one_chip, tmp_path
):
    """Fields of one depth (the schema's: a pad of the depth bucket is
    a zero of the program) whose planes have one shape run ONE
    program of the in-place aggregate whatever rows their fragments
    store and in whichever slots: a field with a sign row and one
    without, slices that met their rows in another order, a write that
    stores a new magnitude row.  The family is bounded by the classes
    of its shapes, not by what was called."""
    holder = Holder(str(tmp_path))
    holder.open()
    v = holder.create_index("i").create_frame("v")
    v.set_options(range_enabled=True)
    v.create_field("a", 0, 7)     # depth 3 -> bucket 8, 8-row planes
    v.create_field("c", -7, 7)    # depth 3 as a, 8-row planes, a sign row
    v.create_field("e", 0, 7)     # as a, its rows stored in another order a slice
    w = bp.SLICE_WIDTH
    v.import_value("a", [1, 2, w + 3], [1, 5, 7])
    v.import_value("c", [1, 2, w + 3], [-1, 2, 1])
    v.import_value("e", [1], [1])
    v.import_value("e", [2], [2])
    v.import_value("e", [w + 1], [2])
    v.import_value("e", [w + 2], [1])
    view = v.view("field_e")
    rows = [0, 2, 3]  # exists, two magnitude rows
    slots = [view.fragment(sl).slots_of(rows)[0] for sl in (0, 1)]
    assert sorted(slots[0]) == sorted(slots[1]) and slots[0] != slots[1]
    _upload_mirrors(holder)
    ex = Executor(holder, tracer=trace.Tracer())
    try:
        plan.clear_program_caches()

        def ask(fld):
            res, spans = traced(ex, f"Sum(frame=v, field={fld})")
            assert spans["bsi.agg"]["tags"]["way"] == "in_place"
            return (res.value, res.count), plan.program_cache_stats()[
                "bitplane.aggregatePlanes"]

        assert ask("a") == ((13, 3), 1)
        assert ask("c") == ((2, 3), 1)
        assert ask("e") == ((6, 4), 1)
        # a new magnitude row in one slice (a scatter brings the mirror up)
        v.import_value("e", [w + 5], [4])
        assert ask("e") == ((10, 5), 1)
        bounds = plan.program_cache_bounds()["bitplane.aggregatePlanes"]
        # one layout x (1, 2 members) x the 8-row class of one unit, a device
        assert bounds == 2 * bp.mesh_device_count()
    finally:
        ex.close()
        holder.close()


def test_an_acknowledged_write_is_read_by_the_next_in_place_sum(one_chip, tmp_path):
    """A point write, a reader that brings the mirror up to date
    (``device_plane``) and an in-place Sum, interleaved: the Sum never
    answers from the array before the write (``fresh_mirror`` reads the
    mirror and its version under the lock that publishes them)."""
    holder = Holder(str(tmp_path))
    holder.open()
    v = holder.create_index("i").create_frame("v")
    v.set_options(range_enabled=True)
    v.create_field("q", 0, 1000)
    v.import_value("q", [1, bp.SLICE_WIDTH + 1], [10, 20])
    _upload_mirrors(holder)
    frags = v.view("field_q").fragments()
    ex = Executor(holder, tracer=trace.Tracer())
    try:
        total = 30
        for step in range(1, 9):
            col = (step % 2) * bp.SLICE_WIDTH + 10 + step
            v.import_value("q", [col], [step])
            total += step
            if step % 3 == 0:  # another reader refreshes the mirror first
                frags[step % 2].device_plane()
            res, spans = traced(ex, "Sum(frame=v, field=q)")
            assert spans["bsi.agg"]["tags"]["way"] == "in_place"
            assert (res.value, res.count) == (total, 2 + step)
            # the mirror is handed out for the version it holds, and no other
            frag = frags[step % 2]
            assert frag.fresh_mirror(frag._version) is frag._device is not None
            assert frag.fresh_mirror(frag._version - 1) is None
    finally:
        ex.close()
        holder.close()


def test_a_launch_that_fails_decodes_hostevals_vectors_and_the_path_heals(one_chip, table):
    """The in-place aggregate rides the health gate of the leaf batch:
    retry, failure, the host's vectors; a denied path goes to the host
    at once; the probe after the window runs in place again."""
    import time

    from pilosa_tpu.device.health import DeviceHealth
    from pilosa_tpu.testing import faults

    holder, t = table
    text = "Sum(Range(frame=v, d >< [-5, 3]), frame=v, field=q)"
    expected = want("Sum", t["q"][(t["d"] >= -5) & (t["d"] <= 3)])
    dh = DeviceHealth(quarantine_threshold=2, open_ms=120, watchdog_ms=0)
    ex = Executor(holder, device_health=dh, tracer=trace.Tracer())
    try:
        res, spans = traced(ex, text)
        assert (res.value, res.count) == expected and "hosteval" not in spans
        faults.install("device.launch:mode=error")
        for _ in range(3):
            res, spans = traced(ex, text)
            assert (res.value, res.count) == expected
            assert "hosteval" in spans
        assert dh.degraded()
        assert "bsi.agg" not in spans  # denied: no device path was prepared
        faults.clear()
        time.sleep(0.15)
        res, spans = traced(ex, text)
        assert (res.value, res.count) == expected
        assert spans["bsi.agg"]["tags"]["way"] == "in_place" and "hosteval" not in spans
        assert not dh.degraded()
    finally:
        faults.clear()
        ex.close()
        dh.close()


def _sparse_holder(tmp_path, monkeypatch):
    """A field whose fragments keep one dense row: the rest of its
    planes live in the sparse tier, which no mirror holds."""
    init = fragment_mod.Fragment.__init__

    def small_budget(self, *a, **kw):
        kw["dense_row_budget"] = 1
        init(self, *a, **kw)

    monkeypatch.setattr(fragment_mod.Fragment, "__init__", small_budget)
    holder = Holder(str(tmp_path))
    holder.open()
    v = holder.create_index("i").create_frame("v")
    v.set_options(range_enabled=True)
    v.create_field("q", 0, 50)
    cols = np.arange(40) * 3
    vals = np.arange(40) % 51
    for s in range(2):
        v.import_value("q", s * bp.SLICE_WIDTH + cols, vals)
    return holder, int(vals.sum()) * 2


def test_a_sparse_tier_plane_takes_the_leaf_batch(one_chip, tmp_path, monkeypatch):
    holder, total = _sparse_holder(tmp_path, monkeypatch)
    ex = Executor(holder, tracer=trace.Tracer())
    try:
        res, spans = traced(ex, "Sum(frame=v, field=q)")
        assert (res.value, res.count) == (total, 80)
        agg = spans["bsi.agg"]["tags"]
        assert (agg["way"], agg["reason"]) == ("batch", "sparse_tier")
        assert "plan" in spans and "bsi.dispatch" not in spans
    finally:
        ex.close()
        holder.close()


def test_cold_mirrors_take_the_leaf_batch_where_it_fits_and_resident_ones_do_not(
    one_chip, tmp_path
):
    """Mostly-cold mirrors: the host fills the rows the answer reads,
    as for a Count, and nothing uploads on the answer's way.  Once the
    mirrors are resident the same text goes in place.  Where the leaf
    batches of such texts would not fit the device's budget beside the
    planes (a fact table's first answers), cold planes upload on the
    way and the answer is computed in place all the same."""
    from pilosa_tpu import device as device_mod

    holder = Holder(str(tmp_path))
    holder.open()
    idx = holder.create_index("i")
    f = idx.create_frame("f")
    v = idx.create_frame("v")
    v.set_options(range_enabled=True)
    v.create_field("q", 0, 50)
    cols = np.arange(200) * 5
    for s in range(2):
        c = s * bp.SLICE_WIDTH + cols
        f.import_bulk(np.arange(200) % 60, c)
        v.import_value("q", c, np.arange(200) % 51)
    ex = Executor(holder, tracer=trace.Tracer())
    pool = device_mod.pool()
    budget = pool._budget
    try:
        def ask(text):
            res, spans = traced(ex, text)
            return res, dict(spans["bsi.prep"]["tags"], **spans["bsi.agg"]["tags"])

        mask = np.arange(200) % 60 == 7
        total = (int((np.arange(200) % 51)[mask].sum()) * 2, int(mask.sum()) * 2)
        text = "Sum(Bitmap(frame=f, rowID=7), frame=v, field=q)"
        res, agg = ask(text)
        assert (res.value, res.count) == total
        assert (agg["way"], agg["reason"]) == ("batch", "cold_mirrors")
        _upload_mirrors(holder)
        res, agg = ask("Sum(Bitmap(frame=f, rowID=8), frame=v, field=q)")
        assert agg["way"] == "in_place" and "reason" not in agg
        # the field's planes cold again after a write
        v.import_value("q", [3], [9])
        for frag in v.view("field_q").fragments():
            frag._invalidate_device()
        res, agg = ask("Sum(frame=v, field=q)")
        assert (agg["way"], agg["reason"]) == ("batch", "cold_mirrors")
        assert res.count == 401
        # a budget that holds the planes but not four leaf batches of
        # 2 slices x 10 leaves beside them: in place, uploading on the way
        for frag in v.view("field_q").fragments():
            frag._invalidate_device()
        pool.configure(budget_bytes=64 * bp.WORDS_PER_SLICE * 4)
        res, agg = ask("Sum(frame=v, field=q)")
        assert agg["way"] == "in_place" and agg["cold"] == 2
        assert res.count == 401
    finally:
        pool.configure(budget_bytes=budget)
        ex.close()
        holder.close()
