"""A fragment's plane is as wide as the columns it holds ask for
(``bp.row_words``: a pow2 class of words, 128 at least), so a row of a
4,096-column frame takes 512 B on the host and on the device, not
128 KiB.  Every PQL call has to answer the same on a narrow fragment as
on one whose columns fill the slice; a write beyond the width re-lays
the plane and loses nothing, also across snapshot, WAL replay and
restart; and the programs over plane mirrors stay bounded.  Everything
here runs on the CPU."""

import os
import signal

import numpy as np
import pytest

from pilosa_tpu.core.fragment import DENSE_PLANE_BYTES, Fragment
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.exec import plan
from pilosa_tpu.exec.executor import Executor
from pilosa_tpu.ops import bitplane as bp
from pilosa_tpu.pql.parser import parse_string

SW = bp.SLICE_WIDTH
ROWS = 40
# in-slice columns of the rows below: under 4,096, so the plane is narrow
COLS = 4000


def bits(seed=5):
    """``{row: sorted in-slice columns}`` over two slices: rows that
    overlap (series of near copies), an empty-ish one, a full one."""
    rng = np.random.default_rng(seed)
    out = {}
    base = np.unique(rng.integers(0, COLS, 60))
    for r in range(ROWS):
        mine = np.unique(np.concatenate([
            rng.choice(base, size=40, replace=False), rng.integers(0, COLS, 8)]))
        out[r] = mine
    out[3] = base  # a parent of the series
    out[ROWS + 5] = np.asarray([7])  # one bit
    return out


@pytest.fixture(scope="module", params=["narrow", "wide"])
def index(request, tmp_path_factory):
    """The same bits in a narrow-plane index and in one whose planes are
    of full width (a far column in a row no text asks about), with a BSI
    field, an inverse view and a second slice."""
    h = Holder(str(tmp_path_factory.mktemp(request.param) / "data"))
    h.open()
    idx = h.create_index("i")
    f = idx.create_frame("f", cache_size=4096, inverse_enabled=True)
    v = idx.create_frame("v")
    v.set_options(range_enabled=True)
    v.create_field("q", -50, 900)
    data = bits()
    for s in (0, 1):
        rows = np.concatenate([np.full(len(c), r) for r, c in data.items()])
        cols = np.concatenate(list(data.values())) + s * SW
        f.import_bulk(rows, cols)
        vcols = np.arange(0, COLS, 7) + s * SW
        v.import_value("q", vcols, (np.arange(len(vcols)) * 13) % 951 - 50)
    if request.param == "wide":
        for s in (0, 1):
            f.set_bit("standard", 999, (s + 1) * SW - 1)
            v.import_value("q", [(s + 1) * SW - 1], [0])
    ex = Executor(h)
    frag = h.fragment("i", "f", "standard", 0)
    want = bp.MIN_ROW_WORDS if request.param == "narrow" else bp.WORDS_PER_SLICE
    assert frag.plane_words() == want
    yield ex, data, request.param
    ex.close()
    h.close()


def ask(ex, text):
    (res,) = ex.execute("i", parse_string(text))
    return res


def cols_of(data, r):
    return np.concatenate([data.get(r, np.zeros(0, int)) + s * SW for s in (0, 1)])


OPS = {"Intersect": np.intersect1d, "Union": np.union1d,
       "Difference": np.setdiff1d, "Xor": np.setxor1d}


@pytest.mark.parametrize("op", sorted(OPS))
def test_count_and_bitmap_of_each_operator(index, op):
    ex, data, _ = index
    want = OPS[op](cols_of(data, 3), cols_of(data, 9))
    text = f"{op}(Bitmap(frame=f, rowID=3), Bitmap(frame=f, rowID=9))"
    assert ask(ex, f"Count({text})") == len(want)
    assert ask(ex, text).bits() == want.tolist()
    # a row the index does not hold, and the one-bit row
    assert ask(ex, "Count(Bitmap(frame=f, rowID=77777))") == 0
    assert ask(ex, f"Bitmap(frame=f, rowID={ROWS + 5})").bits() == [7, SW + 7]


def shared(data, a, b):
    return 2 * len(np.intersect1d(data[a], data[b]))


def test_topn_plain_src_and_ids(index):
    ex, data, _ = index
    rows = [r for r in data if r != 999]
    plain = sorted(((r, 2 * len(data[r])) for r in rows), key=lambda p: (-p[1], p[0]))
    got = [(p.id, p.count) for p in ask(ex, "TopN(frame=f, n=5)")]
    assert got == plain[:5]
    src = sorted(((r, shared(data, r, 3)) for r in rows if shared(data, r, 3)),
                 key=lambda p: (-p[1], p[0]))
    got = [(p.id, p.count) for p in ask(ex, "TopN(Bitmap(frame=f, rowID=3), frame=f, n=7)")]
    assert got == src[:7]
    got = [(p.id, p.count)
           for p in ask(ex, "TopN(Bitmap(frame=f, rowID=3), frame=f, ids=[2, 9, 77777])")]
    assert got == sorted(((r, shared(data, r, 3)) for r in (2, 9)),
                         key=lambda p: (-p[1], p[0]))


@pytest.mark.parametrize("t", [1, 50, 70, 90, 100])
def test_topn_tanimoto(index, t):
    ex, data, _ = index
    s = 2 * len(data[3])
    want = []
    for r in data:
        if r == 999:
            continue
        cnt, c = 2 * len(data[r]), shared(data, r, 3)
        if cnt > s * t / 100 and cnt < s * 100 / t and c > 0 \
                and np.ceil(100 * c / (cnt + s - c)) > t:
            want.append((r, c))
    want.sort(key=lambda p: (-p[1], p[0]))
    text = f"TopN(Bitmap(frame=f, rowID=3), frame=f, n=1000, tanimotoThreshold={t})"
    assert [(p.id, p.count) for p in ask(ex, text)] == want
    assert (t == 100) == (want == [])


def test_range_sum_and_the_inverse_view(index):
    ex, data, _ = index
    vcols = np.arange(0, COLS, 7)
    vals = (np.arange(len(vcols)) * 13) % 951 - 50
    keep = (vals >= 100) & (vals <= 400)
    got = ask(ex, "Sum(Range(frame=v, q >< [100, 400]), frame=v, field=q)")
    assert (got.value, got.count) == (2 * int(vals[keep].sum()), 2 * int(keep.sum()))
    assert ask(ex, "Count(Range(frame=v, q < 0))") == 2 * int((vals < 0).sum())
    under = ask(ex, "Sum(Bitmap(frame=f, rowID=3), frame=v, field=q)")
    hit = np.isin(vcols, data[3])
    assert (under.value, under.count) == (2 * int(vals[hit].sum()), 2 * int(hit.sum()))
    # the inverse view: which rows set column 7 of slice 0
    rows = sorted(r for r, c in data.items() if 7 in c)
    assert ask(ex, "Bitmap(frame=f, columnID=7)").bits() == rows


def test_set_and_clear_bit(index):
    ex, _, _ = index
    assert ask(ex, "SetBit(frame=f, rowID=12345, columnID=11)") is True
    assert ask(ex, "SetBit(frame=f, rowID=12345, columnID=11)") is False
    assert ask(ex, "Count(Bitmap(frame=f, rowID=12345))") == 1
    assert ask(ex, "ClearBit(frame=f, rowID=12345, columnID=11)") is True
    assert ask(ex, "ClearBit(frame=f, rowID=12345, columnID=900000)") is False
    assert ask(ex, "Count(Bitmap(frame=f, rowID=12345))") == 0


# ---------------------------------------------------------------------------
# the layout itself
# ---------------------------------------------------------------------------


def new_fragment(path, **kw):
    f = Fragment(str(path), "i", "f", "standard", 0, **kw)
    f.open()
    return f


def held(frag):
    return {r: frag.row(r).bits() for r in frag.row_counts()}


@pytest.mark.parametrize("rows", [100, 3000])
def test_the_plane_and_its_mirror_take_512_bytes_a_row(tmp_path, rows):
    f = new_fragment(tmp_path / "frag")
    rng = np.random.default_rng(1)
    r = np.repeat(np.arange(rows), 20)
    f.import_bulk(r, rng.integers(0, 4096, len(r)))
    assert f.plane_words() == 128 and f.plane_rows() == bp.pad_rows(rows)
    # N x 512 B rounded to the row class, on the host and on the device
    assert f.plane_nbytes == bp.pad_rows(rows) * 512
    assert f.device_plane().nbytes == f.plane_nbytes
    assert not f.holds_sparse_tier_rows()
    # the dense tier's budget is bytes: 16.8M such rows, 65,536 full ones
    assert f._dense_cap() == DENSE_PLANE_BYTES // 512 == 65536 * 256
    assert f._dense_cap(bp.WORDS_PER_SLICE) == 65536
    f.close()


@pytest.mark.parametrize("far", [4096, 70000, SW - 1])
def test_a_write_beyond_the_width_relays_and_loses_nothing(tmp_path, far):
    f = new_fragment(tmp_path / "frag", max_op_n=10**9)
    rng = np.random.default_rng(far)
    r = np.repeat(np.arange(50), 10)
    f.import_bulk(r, rng.integers(0, 4096, len(r)))
    before, version = held(f), f._version
    f.device_plane()
    assert f.set_bit(7, far) is True
    assert f.plane_words() == bp.row_words(far) > 128 and f._version > version
    before[7] = sorted(before[7] + [far])
    assert held(f) == before
    assert np.asarray(f.device_row(7)).tolist() == f._row_words_host(7).tolist()
    assert f.contains(7, far) and not f.contains(8, far)
    # ... across a restart from the op log, and then from a snapshot
    f.close()
    f = new_fragment(tmp_path / "frag")
    assert held(f) == before and f.plane_words() == bp.row_words(far)
    f.snapshot()
    f.close()
    f = new_fragment(tmp_path / "frag")
    assert held(f) == before
    # a clear beyond the width of what a reload laid out is a no-op
    assert f.clear_bit(7, far) is True and f.clear_bit(7, far) is False
    f.snapshot()
    f.close()
    # the width follows the data: with the far bit gone from the
    # snapshot the reload is narrow
    f = new_fragment(tmp_path / "frag")
    assert f.plane_words() == 128 and 7 in held(f)
    f.close()


def test_a_relay_keeps_to_the_byte_budget(tmp_path):
    """One far column must not turn many narrow rows into as many
    128 KiB ones: rows the budget no longer holds move to the sparse
    tier, and every bit is still there."""
    f = new_fragment(tmp_path / "frag", dense_row_budget=6)
    for r in range(6):
        f.set_bit(r, 10 + r)
    assert len(f._slot_of) == 6
    f.dense_row_budget = 4
    f.set_bit(5, SW - 3)  # row 5 itself is among the rows that move
    assert len(f._slot_of) == 4 and f.holds_sparse_tier_rows()
    assert held(f) == {**{r: [10 + r] for r in range(5)}, 5: [15, SW - 3]}
    assert sum(f.row_counts().values()) == 7
    f.close()


def test_an_import_of_many_new_rows_and_its_snapshot_round_trip(tmp_path):
    f = new_fragment(tmp_path / "frag")
    rng = np.random.default_rng(3)
    r = np.repeat(rng.permutation(5000)[:3000] * 3, 30)  # ids out of order
    c = rng.integers(0, 4096, len(r))
    f.import_bulk(r, c)
    want = {}
    for rr, cc in zip(r.tolist(), c.tolist()):
        want.setdefault(rr, set()).add(cc)
    want = {k: sorted(v) for k, v in want.items()}
    assert held(f) == want
    ids, cnts = f.cache.top_arrays()
    assert dict(zip(ids.tolist(), cnts.tolist())) == {k: len(v) for k, v in want.items()}
    f.close()
    f = new_fragment(tmp_path / "frag")  # from the import's snapshot
    assert held(f) == want and f.plane_words() == 128
    f.close()


def test_wal_replay_after_a_kill_relays_too(tmp_path):
    """``tests/test_crash.py``'s shape: a real server killed after an
    acknowledged write beyond the plane's width; the reboot serves it."""
    from tests.test_crash import _boot_server

    proc, c = _boot_server(tmp_path)
    try:
        c.create_index("i")
        c.create_frame("i", "f")
        for r in range(20):
            c.execute_pql("i", f"SetBit(frame=f, rowID={r}, columnID={r + 1})")
        c.execute_pql("i", "SetBit(frame=f, rowID=4, columnID=500000)")
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait()
        proc, c = _boot_server(tmp_path)
        assert c.execute_pql("i", "Bitmap(frame=f, rowID=4)").bits() == [
            5, 500000]
        assert c.execute_pql("i", "Count(Bitmap(frame=f, rowID=19))") == 1
    finally:
        proc.kill()
        proc.wait()


def test_the_walked_scorers_programs_are_bounded_whatever_ran_before(tmp_path):
    """``bitplane.scoreRows``: a program a (row class, width class) and
    a device, never one a row count, a src or a threshold."""
    h = Holder(str(tmp_path))
    h.open()
    f = h.create_index("i").create_frame("f", cache_size=100000)
    rng = np.random.default_rng(9)
    ex = Executor(h)
    try:
        before = plan.program_cache_stats()["bitplane.scoreRows"]
        seen = set()
        for rows in (10, 12, 70, 300, 513):
            r = np.repeat(np.arange(rows), 12)
            f.import_bulk(r, rng.integers(0, 4096, len(r)))
            for src, t in ((1, 70), (2, 90), (3, 0)):
                extra = f", tanimotoThreshold={t}" if t else ""
                ex.execute("i", parse_string(
                    f"TopN(Bitmap(frame=f, rowID={src}), frame=f, n=5{extra})"))
            seen.add(bp.pad_rows(rows))
        stats, bounds = plan.program_cache_stats(), plan.program_cache_bounds()
        assert stats["bitplane.scoreRows"] - before <= len(seen) == 4
        for family, n in stats.items():
            if family in bounds:
                assert n <= bounds[family], family
    finally:
        ex.close()
        h.close()
