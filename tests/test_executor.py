"""Executor tests — single-node and mocked-remote map/reduce
(parity tier for executor_test.go)."""

import os
from datetime import datetime

import jax
import pytest

from pilosa_tpu.cluster.topology import new_cluster
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.core.view import VIEW_INVERSE, VIEW_STANDARD
from pilosa_tpu.exec import (
    ExecOptions,
    Executor,
    ExecutorError,
    SlicesUnavailableError,
    TooManyWritesError,
)
from pilosa_tpu.ops.bitplane import SLICE_WIDTH
from pilosa_tpu.pql.parser import parse_string


@pytest.fixture
def holder(tmp_path):
    h = Holder(str(tmp_path / "data"))
    h.open()
    yield h
    h.close()


@pytest.fixture
def ex(holder):
    """Single-node executor pinned to node 0 (reference:
    executor_test.go:758-770)."""
    c = new_cluster(1)
    return Executor(holder, host=c.nodes[0].host, cluster=c)


def must_set_bits(holder, index, frame, bits, view=VIEW_STANDARD):
    idx = holder.create_index_if_not_exists(index)
    f = idx.create_frame_if_not_exists(frame)
    for row, col in bits:
        f.set_bit(view, row, col)
    return f


def q(ex, index, pql, slices=None, opt=None):
    return ex.execute(index, parse_string(pql), slices, opt)


# --- bitmap reads (reference: executor_test.go:31-205) ---------------------


def test_execute_bitmap(ex, holder):
    f = must_set_bits(
        holder, "i", "f", [(10, 3), (10, SLICE_WIDTH + 1)]
    )
    f.row_attr_store.set_attrs(10, {"foo": "bar", "baz": 123})
    (bm,) = q(ex, "i", "Bitmap(rowID=10, frame=f)")
    assert bm.bits() == [3, SLICE_WIDTH + 1]
    assert bm.attrs == {"foo": "bar", "baz": 123}


def test_execute_bitmap_default_frame(ex, holder):
    must_set_bits(holder, "i", "general", [(10, 3)])
    (bm,) = q(ex, "i", "Bitmap(rowID=10)")
    assert bm.bits() == [3]


def test_execute_intersect_difference_union_count(ex, holder):
    must_set_bits(
        holder,
        "i",
        "f",
        [(10, 0), (10, 1), (10, SLICE_WIDTH + 2), (11, 1), (11, SLICE_WIDTH + 2)],
    )
    (bm,) = q(ex, "i", "Intersect(Bitmap(rowID=10, frame=f), Bitmap(rowID=11, frame=f))")
    assert bm.bits() == [1, SLICE_WIDTH + 2]
    (bm,) = q(ex, "i", "Union(Bitmap(rowID=10, frame=f), Bitmap(rowID=11, frame=f))")
    assert bm.bits() == [0, 1, SLICE_WIDTH + 2]
    (bm,) = q(ex, "i", "Difference(Bitmap(rowID=10, frame=f), Bitmap(rowID=11, frame=f))")
    assert bm.bits() == [0]
    (n,) = q(ex, "i", "Count(Union(Bitmap(rowID=10, frame=f), Bitmap(rowID=11, frame=f)))")
    assert n == 3


def test_execute_nested_tree(ex, holder):
    must_set_bits(holder, "i", "f", [(1, 1), (1, 2), (2, 2), (2, 3), (3, 3)])
    (n,) = q(
        ex,
        "i",
        "Count(Union(Intersect(Bitmap(rowID=1, frame=f), Bitmap(rowID=2, frame=f)),"
        " Bitmap(rowID=3, frame=f)))",
    )
    assert n == 2  # {2} | {3}


def test_execute_empty_intersect_errors(ex, holder):
    must_set_bits(holder, "i", "f", [(1, 1)])
    with pytest.raises(Exception, match="empty Intersect"):
        q(ex, "i", "Count(Intersect())")


def test_execute_count_requires_child(ex, holder):
    must_set_bits(holder, "i", "f", [(1, 1)])
    with pytest.raises(ExecutorError, match="requires an input"):
        q(ex, "i", "Count()")


def test_bitmap_missing_row_and_col(ex, holder):
    must_set_bits(holder, "i", "f", [(1, 1)])
    with pytest.raises(ExecutorError, match="must specify"):
        q(ex, "i", "Bitmap(frame=f)")


def test_inverse_bitmap(ex, holder):
    idx = holder.create_index("i")
    f = idx.create_frame("f", inverse_enabled=True)
    # Writing through the executor populates both orientations.
    q(ex, "i", "SetBit(frame=f, rowID=10, columnID=3)")
    q(ex, "i", "SetBit(frame=f, rowID=11, columnID=3)")
    (bm,) = q(ex, "i", "Bitmap(columnID=3, frame=f)")
    assert bm.bits() == [10, 11]


def test_inverse_requires_enabled(ex, holder):
    must_set_bits(holder, "i", "f", [(1, 1)])
    with pytest.raises(ExecutorError, match="inverse storage enabled"):
        q(ex, "i", "Bitmap(columnID=1, frame=f)")


# --- writes ----------------------------------------------------------------


def test_set_and_clear_bit(ex, holder):
    holder.create_index("i").create_frame("f")
    (changed,) = q(ex, "i", "SetBit(frame=f, rowID=1, columnID=9)")
    assert changed is True
    (changed,) = q(ex, "i", "SetBit(frame=f, rowID=1, columnID=9)")
    assert changed is False
    (n,) = q(ex, "i", "Count(Bitmap(rowID=1, frame=f))")
    assert n == 1
    (changed,) = q(ex, "i", "ClearBit(frame=f, rowID=1, columnID=9)")
    assert changed is True
    (n,) = q(ex, "i", "Count(Bitmap(rowID=1, frame=f))")
    assert n == 0


def test_setbit_with_timestamp_and_range(ex, holder):
    idx = holder.create_index("i")
    idx.create_frame("f", time_quantum="YMDH")
    q(ex, "i", 'SetBit(frame=f, rowID=1, columnID=2, timestamp="2010-01-01T00:00")')
    q(ex, "i", 'SetBit(frame=f, rowID=1, columnID=3, timestamp="2010-03-01T00:00")')
    q(ex, "i", 'SetBit(frame=f, rowID=1, columnID=4, timestamp="2011-01-01T00:00")')
    (bm,) = q(
        ex, "i",
        'Range(rowID=1, frame=f, start="2010-01-01T00:00", end="2010-12-31T23:59")',
    )
    assert bm.bits() == [2, 3]


def test_set_row_attrs(ex, holder):
    holder.create_index("i").create_frame("f")
    q(ex, "i", 'SetRowAttrs(frame=f, rowID=7, alpha="beta", n=123)')
    assert holder.frame("i", "f").row_attr_store.attrs(7) == {"alpha": "beta", "n": 123}


def test_bulk_set_row_attrs(ex, holder):
    holder.create_index("i").create_frame("f")
    res = q(ex, "i", 'SetRowAttrs(frame=f, rowID=1, a=1) SetRowAttrs(frame=f, rowID=2, b=2)')
    assert res == [None, None]
    store = holder.frame("i", "f").row_attr_store
    assert store.attrs(1) == {"a": 1}
    assert store.attrs(2) == {"b": 2}


def test_set_column_attrs(ex, holder):
    holder.create_index("i")
    q(ex, "i", 'SetColumnAttrs(id=99, x="y")')
    assert holder.index("i").column_attr_store.attrs(99) == {"x": "y"}


def test_max_writes_guard(holder):
    c = new_cluster(1)
    e = Executor(holder, host=c.nodes[0].host, cluster=c, max_writes_per_request=2)
    holder.create_index("i").create_frame("f")
    pql = " ".join(f"SetBit(frame=f, rowID=1, columnID={i})" for i in range(3))
    with pytest.raises(TooManyWritesError):
        q(e, "i", pql)


# --- TopN (reference: executor_test.go:207-376) ----------------------------


def test_topn(ex, holder):
    bits = [(0, i) for i in range(5)] + [(10, i) for i in range(3)] + [(12, 5)]
    bits += [(0, SLICE_WIDTH + i) for i in range(2)]
    must_set_bits(holder, "i", "f", bits)
    (pairs,) = q(ex, "i", "TopN(frame=f, n=2)")
    assert [(p.id, p.count) for p in pairs] == [(0, 7), (10, 3)]


def test_topn_with_src(ex, holder):
    must_set_bits(
        holder, "i", "f",
        [(0, 0), (0, 1), (0, 2), (10, 1), (10, 2), (12, 2)],
    )
    (pairs,) = q(ex, "i", "TopN(Bitmap(rowID=0, frame=f), frame=f, n=2)")
    assert [(p.id, p.count) for p in pairs] == [(0, 3), (10, 2)]


def test_topn_src_many_slices(ex, holder):
    """TopN with a src bitmap spanning MANY slices: the executor
    prepares every slice then resolves all dense score vectors in one
    bulk fetch — counts must equal the per-slice sum of |row ∩ src|
    exactly (two-phase refetch included)."""
    n_slices = 9
    bits = []
    # src row 0: columns 0..9 of every slice EXCEPT slice 4 (that
    # fragment never exists — prepare must skip it); rows 1..3 overlap
    # differently per slice.
    for s in range(n_slices):
        if s == 4:
            continue
        base = s * SLICE_WIDTH
        bits += [(0, base + c) for c in range(10)]
        bits += [(1, base + c) for c in range(0, 10, 2)]        # 5/slice
        bits += [(2, base + c) for c in range(0, 10, 3)]        # 4/slice
        if s % 2 == 0:
            bits += [(3, base + c) for c in range(10)]          # 10 on even slices
    must_set_bits(holder, "i", "f", bits)
    (pairs,) = q(ex, "i", "TopN(Bitmap(rowID=0, frame=f), frame=f, n=4)")
    got = {p.id: p.count for p in pairs}
    populated = n_slices - 1  # slice 4 has no fragment at all
    assert got[0] == 10 * populated
    assert got[3] == 10 * 4   # even slices 0,2,6,8
    assert got[1] == 5 * populated
    assert got[2] == 4 * populated


def test_topn_ids(ex, holder):
    must_set_bits(holder, "i", "f", [(0, 0), (0, 1), (10, 1), (12, 2)])
    (pairs,) = q(ex, "i", "TopN(frame=f, ids=[0, 12])")
    assert [(p.id, p.count) for p in pairs] == [(0, 2), (12, 1)]


def test_topn_fill(ex, holder):
    """reference: executor_test.go:328-349 TestExecutor_Execute_TopN_fill
    — the global winner needs exact counts summed across slices even
    when per-slice phase-1 lists disagree."""
    must_set_bits(
        holder, "i", "f",
        [(0, 0), (0, 1), (0, 2),
         (0, SLICE_WIDTH), (1, SLICE_WIDTH + 2), (1, SLICE_WIDTH)],
    )
    (pairs,) = q(ex, "i", "TopN(frame=f, n=1)")
    assert [(p.id, p.count) for p in pairs] == [(0, 4)]


def test_topn_fill_small(ex, holder):
    """reference: executor_test.go:352-382 TestExecutor_Execute_TopN_
    fill_small — a row that is never any single slice's per-slice
    winner by margin still wins globally once counts are summed."""
    bits = [(0, s * SLICE_WIDTH) for s in range(5)]
    bits += [(1, 0), (1, 1)]
    bits += [(2, SLICE_WIDTH), (2, SLICE_WIDTH + 1)]
    bits += [(3, 2 * SLICE_WIDTH), (3, 2 * SLICE_WIDTH + 1)]
    bits += [(4, 3 * SLICE_WIDTH), (4, 3 * SLICE_WIDTH + 1)]
    must_set_bits(holder, "i", "f", bits)
    (pairs,) = q(ex, "i", "TopN(frame=f, n=1)")
    assert [(p.id, p.count) for p in pairs] == [(0, 5)]


def test_read_calls_counted_with_index_tag(ex, holder):
    """Read calls fire a per-call-name counter tagged index:<name>
    (reference: executor.go:163-181, stats_test.go:75-131)."""
    must_set_bits(holder, "i", "f", [(0, 0), (0, 1)])
    calls = []

    class Spy:
        def count_with_custom_tags(self, name, value, tags):
            calls.append((name, value, tuple(tags)))

        def __getattr__(self, _):
            return lambda *a, **k: None

    holder.stats = Spy()
    q(ex, "i", "TopN(frame=f, n=1)")
    q(ex, "i", "Count(Bitmap(rowID=0, frame=f))")
    q(ex, "i", "Bitmap(rowID=0, frame=f)")
    assert ("TopN", 1, ("index:i",)) in calls
    assert ("Count", 1, ("index:i",)) in calls
    assert ("Bitmap", 1, ("index:i",)) in calls


def test_topn_fused_scorer_group_padding(ex, holder):
    """A 5-slice group pads to the 8-bucket in the fused scorer; the
    surplus (repeated) members' scores must never leak into results,
    and a repeat query returns identical pairs."""
    bits = []
    for s in range(5):
        base = s * SLICE_WIDTH
        for r in range(6):
            bits += [(r, base + k) for k in range(r + 2)]
    must_set_bits(holder, "i", "f", bits)
    pql = "TopN(Bitmap(rowID=0, frame=f), frame=f, n=4)"
    (want,) = q(ex, "i", pql)
    assert want
    # row r intersects row 0 on min(r+2, 2) = 2 columns per slice.
    got = {p.id: p.count for p in want}
    assert got[0] == 10  # |row0| = 2 bits x 5 slices
    assert all(v == 10 for v in got.values())
    (again,) = q(ex, "i", pql)
    assert [(p.id, p.count) for p in again] == [(p.id, p.count) for p in want]


def test_topn_src_mutated_falls_back_to_snapshot(ex, holder):
    """When no same-plane src slot is available (different src frame,
    sparse-tier src row, or a mirror refresh since the prepare
    snapshot), the scorer falls back to the one host-snapshot src
    transfer; forcing that path must produce exactly the same
    results."""
    bits = []
    for s in range(3):
        base = s * SLICE_WIDTH
        bits += [(0, base), (0, base + 1), (1, base), (2, base + 1)]
    must_set_bits(holder, "i", "f", bits)

    # Drop every same-plane src slot so the host-snapshot path runs.
    orig = ex._attach_dev_src

    def attach_force_host_src(*args):
        st, sub, srcw, _slot = orig(*args)
        return st, sub, srcw, None

    ex._attach_dev_src = attach_force_host_src
    try:
        (pairs,) = q(ex, "i", "TopN(Bitmap(rowID=0, frame=f), frame=f, n=3)")
    finally:
        ex._attach_dev_src = orig
    got = {p.id: p.count for p in pairs}
    # row0 ∩ row0 = 6 bits; row1 ∩ row0 = 3 (col 0 per slice);
    # row2 ∩ row0 = 3 (col 1 per slice)
    assert got == {0: 6, 1: 3, 2: 3}


def test_topn_duplicate_ids_not_double_counted(ex, holder):
    """A duplicated explicit id must not be scored twice (the cross-
    slice merge SUMS counts by id, so a duplicate would double the
    reported count)."""
    must_set_bits(holder, "i", "f", [(0, 0), (0, 1), (0, 2)])
    (pairs,) = q(ex, "i", "TopN(frame=f, ids=[0, 0])")
    assert [(p.id, p.count) for p in pairs] == [(0, 3)]


def test_topn_tanimoto_bounds(ex, holder):
    must_set_bits(holder, "i", "f", [(0, 0)])
    with pytest.raises(ExecutorError, match="Tanimoto"):
        q(ex, "i", "TopN(Bitmap(rowID=0, frame=f), frame=f, n=2, tanimotoThreshold=150)")


# --- remote fan-out with a mock client (reference:
# executor_test.go:520-745 TestExecutor_Execute_Remote_*) -------------------


class MockClient:
    """Function-mock internal client (reference: handler_test.go:964-974
    HandlerExecutor.ExecuteFn pattern)."""

    def __init__(self, fn):
        self.fn = fn
        self.calls = []

    def execute_query(self, index, query, slices, remote):
        self.calls.append((index, query, list(slices or []), remote))
        return self.fn(index, query, slices, remote)


def test_remote_count_merges(holder):
    """Coordinator sends the sub-query with the peer's slice list and sums
    remote + local counts."""
    c = new_cluster(2)
    holder.create_index("i").create_frame("f")
    # Make local data on the slices owned by node 0.
    local_slices = c.owns_slices("i", 2, c.nodes[0].host)
    remote_slices = [s for s in range(3) if s not in local_slices]
    f = holder.frame("i", "f")
    for s in local_slices:
        f.set_bit(VIEW_STANDARD, 10, s * SLICE_WIDTH + 1)
    # Grow max_slice so the executor fans out over slices 0..2.
    holder.index("i").set_remote_max_slice(2)

    client = MockClient(lambda index, query, slices, remote: [len(slices or [])])
    e = Executor(
        holder, host=c.nodes[0].host, cluster=c, client_factory=lambda node: client
    )
    (n,) = e.execute("i", parse_string("Count(Bitmap(rowID=10, frame=f))"))
    # local bits + mock's per-slice 1
    assert n == len(local_slices) + len(remote_slices)
    assert client.calls, "remote node should have been queried"
    _, query, slices, remote = client.calls[0]
    assert remote is True
    assert sorted(slices) == sorted(remote_slices)
    assert query == "Count(Bitmap(frame=\"f\", rowID=10))"


def test_remote_failure_fails_over_to_replica(holder):
    """A failed node's slices re-map to replicas (reference:
    executor.go:1186-1197)."""
    c = new_cluster(2)
    c.replica_n = 2  # every slice has both nodes
    holder.create_index("i").create_frame("f")
    f = holder.frame("i", "f")
    for s in range(3):
        f.set_bit(VIEW_STANDARD, 10, s * SLICE_WIDTH + 1)

    def fail(index, query, slices, remote):
        raise ConnectionError("remote down")

    client = MockClient(fail)
    e = Executor(
        holder, host=c.nodes[0].host, cluster=c, client_factory=lambda node: client
    )
    (n,) = e.execute("i", parse_string("Count(Bitmap(rowID=10, frame=f))"))
    assert n == 3  # all slices answered locally via replica failover


def test_remote_unavailable_without_replica(holder):
    c = new_cluster(2)  # replica_n = 1
    holder.create_index("i").create_frame("f")
    holder.index("i").set_remote_max_slice(4)

    def fail(index, query, slices, remote):
        raise ConnectionError("remote down")

    e = Executor(
        holder, host=c.nodes[0].host, cluster=c,
        client_factory=lambda node: MockClient(fail),
    )
    # Fail-fast contract: with no surviving replica the query errors
    # naming exactly the unreachable slices (and the causing error).
    remote = c.owns_slices("i", 4, c.nodes[1].host)
    with pytest.raises(SlicesUnavailableError) as ei:
        e.execute("i", parse_string("Count(Bitmap(rowID=10, frame=f))"))
    assert ei.value.slices == sorted(remote)
    assert "remote down" in str(ei.value)


def test_remote_opt_executes_local_only(holder):
    """opt.remote=True must only touch local slices (reference:
    executor.go:1165-1169)."""
    c = new_cluster(2)
    holder.create_index("i").create_frame("f")
    f = holder.frame("i", "f")
    local = c.owns_slices("i", 3, c.nodes[0].host)
    for s in range(4):
        f.set_bit(VIEW_STANDARD, 10, s * SLICE_WIDTH + 1)

    boom = MockClient(lambda *a: (_ for _ in ()).throw(AssertionError("must not call")))
    e = Executor(holder, host=c.nodes[0].host, cluster=c, client_factory=lambda n: boom)
    (n,) = e.execute(
        "i", parse_string("Count(Bitmap(rowID=10, frame=f))"),
        slices=local, opt=ExecOptions(remote=True),
    )
    assert n == len(local)
    assert not boom.calls


def test_inverse_high_cardinality_past_old_row_cap(ex, holder):
    """An inverse-enabled frame over a high-cardinality slice: one bulk
    import touching 70k distinct columns gives the inverse fragment 70k
    distinct rows — past the old 2^16 dense cap — stored in the sparse
    tier; Bitmap on the inverse view still answers (VERDICT r2 item 4).
    (Budget shrunk so the test exercises the spill without 8 GiB.)"""
    import pilosa_tpu.core.fragment as fr

    idx = holder.create_index("i")
    f = idx.create_frame("f", inverse_enabled=True)
    n = 70_000
    rows = [7] * n + [8]
    cols = list(range(n)) + [999_999]  # row 8's column is outside row 7's range
    inv_frag_budget = 512
    orig_init = fr.Fragment.__init__

    def small_init(self, *a, **kw):
        kw.setdefault("dense_row_budget", inv_frag_budget)
        orig_init(self, *a, **kw)

    # shrink the budget for fragments created during this import
    fr.Fragment.__init__ = small_init
    try:
        f.import_bulk(rows, cols)
    finally:
        fr.Fragment.__init__ = orig_init

    inv = holder.fragment("i", "f", VIEW_INVERSE, 0)
    assert inv is not None
    assert len(inv._sparse) >= n - inv_frag_budget
    assert inv._plane.shape[0] <= inv_frag_budget
    # inverse query: all original rows with the column set
    (bm,) = q(ex, "i", "Bitmap(columnID=999999, frame=f)")
    assert bm.bits() == [8]
    (bm,) = q(ex, "i", "Bitmap(columnID=123, frame=f)")
    assert bm.bits() == [7]
    (bm,) = q(ex, "i", "Bitmap(columnID=69999, frame=f)")
    assert bm.bits() == [7]
    # standard orientation still healthy
    (cnt,) = q(ex, "i", "Count(Bitmap(rowID=7, frame=f))")
    assert cnt == n
    # anti-entropy surface over the tall inverse fragment
    # (70k contiguous rows -> blocks 0..699, plus row 999999's block)
    assert len(inv.blocks()) == n // 100 + 1


# --- assembled leaf-batch cache (VERDICT r2 weak #6 / item 3) ---------------


def test_batch_cache_hit_and_invalidation(ex, holder, monkeypatch):
    """A repeated query reuses the assembled device batch (no per-slice
    re-gather); any fragment write invalidates it via the global write
    epoch; results stay correct."""
    must_set_bits(holder, "i", "f", [(1, 3), (1, SLICE_WIDTH + 7), (2, 3)])

    gathers = []
    orig_dev = Executor._assemble_gather_batch
    orig_host = Executor._assemble_mesh_batch_host

    def spy_dev(self, leaves, slices, sweep, mesh):
        built = orig_dev(self, leaves, slices, sweep, mesh)
        if built is not None:
            gathers.append("device")
        return built

    def spy_host(self, index, leaves, slices, mesh):
        gathers.append("host")
        return orig_host(self, index, leaves, slices, mesh)

    # Assembly has two entry points (the plane gather for resident
    # mirrors, host blocks for cold fragments); the cache must avoid BOTH.
    monkeypatch.setattr(Executor, "_assemble_gather_batch", spy_dev)
    monkeypatch.setattr(Executor, "_assemble_mesh_batch_host", spy_host)

    pql = "Count(Intersect(Bitmap(rowID=1, frame=f), Bitmap(rowID=2, frame=f)))"
    assert q(ex, "i", pql) == [1]
    assert len(gathers) == 1
    assert q(ex, "i", pql) == [1]          # cache hit: no second gather
    assert len(gathers) == 1
    # Count() strips to its child, so the bare Intersect query shares
    # the same canonical-call entry — batch reused across reduce kinds
    (bm,) = q(ex, "i", "Intersect(Bitmap(rowID=1, frame=f), Bitmap(rowID=2, frame=f))")
    assert bm.bits() == [3]
    assert len(gathers) == 1
    # a write anywhere bumps the epoch and re-validates -> rebuild
    q(ex, "i", "SetBit(frame=f, rowID=2, columnID=" + str(SLICE_WIDTH + 7) + ")")
    assert q(ex, "i", pql) == [2]
    assert len(gathers) == 2


def test_batch_cache_unrelated_write_revalidates_without_rebuild(ex, holder):
    """A write to an UNRELATED index moves the epoch but the version
    vector still matches — the entry revalidates without re-gathering."""
    must_set_bits(holder, "i", "f", [(1, 3)])
    must_set_bits(holder, "j", "f", [(1, 5)])
    pql = "Count(Bitmap(rowID=1, frame=f))"
    assert q(ex, "i", pql) == [1]
    ent_before = next(iter(ex._batch_cache.values()))["batch"]
    q(ex, "j", 'SetBit(frame=f, rowID=9, columnID=1)')
    assert q(ex, "i", pql) == [1]
    # same batch object reused (revalidated, not rebuilt)
    for key, ent in ex._batch_cache.items():
        if key[0] == "i":
            assert ent["batch"] is ent_before


def test_batch_cache_range_leaves_cached_and_write_invalidated(ex, holder):
    """Range batches cache like Bitmap batches (their validity entries
    carry the quantum + every time-view fragment's version); a write
    into a time view must invalidate them."""
    idx = holder.create_index("i")
    idx.create_frame("f", time_quantum="YMDH")
    q(ex, "i", 'SetBit(frame=f, rowID=1, columnID=2, timestamp="2010-01-01T00:00")')
    pql = ('Count(Range(rowID=1, frame=f, start="2010-01-01T00:00",'
           ' end="2010-12-31T23:59"))')
    assert q(ex, "i", pql) == [1]
    assert any(key[1].find("Range") != -1 for key in ex._batch_cache)
    assert q(ex, "i", pql) == [1]  # warm: served from the cached batch
    q(ex, "i", 'SetBit(frame=f, rowID=1, columnID=7, timestamp="2010-06-15T00:00")')
    assert q(ex, "i", pql) == [2]


def test_batch_cache_range_invalidated_by_quantum_change(ex, holder):
    """set_time_quantum changes which views a Range reads — it bumps
    the write epoch so cached Range batches revalidate."""
    idx = holder.create_index("i")
    f = idx.create_frame("f", time_quantum="YMDH")
    q(ex, "i", 'SetBit(frame=f, rowID=1, columnID=2, timestamp="2010-01-01T00:00")')
    pql = ('Count(Range(rowID=1, frame=f, start="2010-01-01T00:00",'
           ' end="2010-12-31T23:59"))')
    assert q(ex, "i", pql) == [1]
    f.set_time_quantum("Y")
    # The partial-year range can no longer be covered by whole-year
    # views (reference: time.go:95-167 ViewsByTimeRange semantics), so
    # a STALE cached batch returning [1] would be the bug here.
    assert q(ex, "i", pql) == [0]
    year_pql = ('Count(Range(rowID=1, frame=f, start="2010-01-01T00:00",'
                ' end="2011-01-01T00:00"))')
    # The year-aligned range reads the Y view the SetBit fan-out wrote.
    assert q(ex, "i", year_pql) == [1]


def test_batch_cache_invalidated_by_frame_delete(ex, holder):
    """Deleting a frame bumps the write epoch (via fragment close), so
    a cached batch can never serve deleted data (code-review regression,
    r3)."""
    must_set_bits(holder, "i", "f", [(1, 3)])
    pql = "Count(Bitmap(rowID=1, frame=f))"
    assert q(ex, "i", pql) == [1]
    holder.index("i").delete_frame("f")
    with pytest.raises(ExecutorError, match="frame not found"):
        q(ex, "i", pql)
    holder.index("i").create_frame("f")
    assert q(ex, "i", pql) == [0]


def test_concurrent_multislice_topn_and_writes(ex, holder):
    """Parallel MULTI-SLICE src TopN racing writers: the fused scorer
    reads plane SNAPSHOTS captured under each fragment's lock, so every
    result must be internally consistent (sorted, exact after
    quiesce) even while the mirrors refresh under it."""
    import threading

    for s in range(4):
        base = s * SLICE_WIDTH
        for r in range(6):
            must_set_bits(
                holder, "i", "f", [(r, base + c) for c in range(0, 10 + r)]
            )
    errors = []

    def reader():
        try:
            for _ in range(15):
                (pairs,) = q(
                    ex, "i", "TopN(Bitmap(rowID=0, frame=f), frame=f, n=4)"
                )
                counts = [p.count for p in pairs]
                assert counts == sorted(counts, reverse=True)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    def writer():
        try:
            for c in range(50, 90):
                q(ex, "i", f"SetBit(frame=f, rowID=2, columnID={c})")
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=reader) for _ in range(3)] + [
        threading.Thread(target=writer)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    # Quiesced: exact counts (row0 has 10 cols/slice, all within row0's
    # own columns -> |rowX ∩ row0| = 10 per slice for rows whose column
    # range covers row0's).
    (pairs,) = q(ex, "i", "TopN(Bitmap(rowID=0, frame=f), frame=f, n=6)")
    got = {p.id: p.count for p in pairs}
    assert got[0] == 40  # 10 x 4 slices


def test_concurrent_topn_and_writes(ex, holder):
    """Parallel TopN queries racing writes on the SAME fragment: the
    device score fetch runs outside the fragment lock (core/fragment.py
    top()), so this exercises the snapshot consistency of the gathered
    submatrix under mutation.  Every result must be internally
    consistent (sorted, counts from SOME consistent plane state)."""
    import threading

    for r in range(8):
        must_set_bits(holder, "i", "f", [(r, c) for c in range(0, 40 + r, 2)])
    must_set_bits(holder, "i", "f", [(99, c) for c in range(60)])
    errors = []

    def topn_reader():
        try:
            for _ in range(25):
                (pairs,) = q(
                    ex, "i", "TopN(Bitmap(rowID=99, frame=f), frame=f, n=5)"
                )
                counts = [p.count for p in pairs]
                assert counts == sorted(counts, reverse=True)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    def writer(row):
        try:
            for c in range(100, 140):
                q(ex, "i", f"SetBit(frame=f, rowID={row}, columnID={c})")
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=topn_reader) for _ in range(3)] + [
        threading.Thread(target=writer, args=(3,)),
        threading.Thread(target=writer, args=(5,)),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    # Quiesced: exact final scores.
    (pairs,) = q(ex, "i", "TopN(Bitmap(rowID=99, frame=f), frame=f, n=5)")
    by_id = {p.id: p.count for p in pairs}
    # rows 3 and 5 now have all even cols in [0,40+r) plus [100,140).
    assert by_id[3] == len(set(range(0, 43, 2)) & set(range(60)))
    assert by_id[5] == len(set(range(0, 45, 2)) & set(range(60)))


def test_concurrent_queries_and_writes(ex, holder):
    """Smoke: concurrent queries and writes through one executor (the
    HTTP server is threaded) never crash on the cache paths, and the
    final count is exact."""
    import threading

    must_set_bits(holder, "i", "f", [(1, c) for c in range(50)])
    must_set_bits(holder, "i", "f", [(2, c) for c in range(0, 50, 2)])
    errors = []

    def reader():
        try:
            for _ in range(40):
                (n,) = q(ex, "i",
                         "Count(Intersect(Bitmap(rowID=1, frame=f),"
                         " Bitmap(rowID=2, frame=f)))")
                assert n >= 25
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    def writer(base):
        try:
            for c in range(base, base + 40):
                q(ex, "i", f"SetBit(frame=f, rowID=1, columnID={c})")
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=reader) for _ in range(3)] + [
        threading.Thread(target=writer, args=(100,)),
        threading.Thread(target=writer, args=(200,)),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    (n,) = q(ex, "i", "Count(Bitmap(rowID=1, frame=f))")
    assert n == 50 + 80


@pytest.mark.parametrize(
    "tree",
    [
        "Bitmap(rowID=0, frame=f)",
        "Intersect(Bitmap(rowID=0, frame=f), Bitmap(rowID=1, frame=f))",
        "Union(Bitmap(rowID=0, frame=f), Bitmap(rowID=9, frame=f))",
        "Difference(Bitmap(rowID=0, frame=f), Bitmap(rowID=1, frame=f))",
        "Xor(Bitmap(rowID=9, frame=f), Bitmap(rowID=1, frame=f))",
        "Intersect(Bitmap(rowID=9, frame=f), Bitmap(rowID=1, frame=f))",
        "Difference(Bitmap(rowID=9, frame=f), Bitmap(rowID=1, frame=f))",
        "Union(Intersect(Bitmap(rowID=0, frame=f), Bitmap(rowID=1, frame=f)),"
        " Xor(Bitmap(rowID=2, frame=f), Bitmap(rowID=9, frame=f)))",
    ],
)
def test_eval_expr_np_matches_device(ex, holder, tree):
    """The host (numpy) tree evaluator used for TopN src rows must stay
    bit-identical to the device path — including the None (= absent
    row) propagation rules.  rowID=9 never has bits, so every op's
    empty-operand branch is exercised."""
    import numpy as np

    must_set_bits(holder, "i", "f", [(0, c) for c in range(0, 64, 3)])
    must_set_bits(holder, "i", "f", [(1, c) for c in range(0, 64, 2)])
    must_set_bits(holder, "i", "f", [(2, c) for c in range(5, 40)])

    call = parse_string(tree).calls[0]
    host_rows = ex._eval_tree_slices_host("i", call, [0])
    dev_rows = ex._eval_tree_slices("i", call, [0], "row")

    hr, dr = host_rows[0], dev_rows.get(0)
    if hr is None:
        assert dr is None or not np.asarray(dr).any()
    else:
        want = np.zeros_like(hr) if dr is None else np.asarray(dr)
        np.testing.assert_array_equal(hr, want)


def test_topn_single_slice_skips_phase2(ex, holder, monkeypatch):
    """With one slice, phase-1 TopN scores are already exact and
    complete, so the executor skips the phase-2 refetch (half the
    device round trips); results must equal the two-phase output."""
    must_set_bits(
        holder, "i", "f",
        [(0, c) for c in range(8)] + [(1, c) for c in range(0, 8, 2)]
        + [(2, 1), (2, 2)],
    )
    calls = []
    orig = Executor._execute_topn_slices

    def spy(self, index, c, slices, opt):
        calls.append(str(c))
        return orig(self, index, c, slices, opt)

    monkeypatch.setattr(Executor, "_execute_topn_slices", spy)
    (pairs,) = q(ex, "i", "TopN(Bitmap(rowID=0, frame=f), frame=f, n=2)")
    assert [(p.id, p.count) for p in pairs] == [(0, 8), (1, 4)]
    # no phase-2 pass, and since PR 36 no per-slice pass either: one slice
    # is folded as many are (both phases from one scoring pass)
    assert calls == []


def test_topn_inverse_orientation(ex, holder):
    """TopN(inverse=true) ranks COLUMNS by row overlap using the
    inverse views' own slice list (reference: executor.go:336-344
    SupportsInverse slice-list swap)."""
    idx = holder.create_index("i")
    idx.create_frame("f", inverse_enabled=True)
    # col 5 appears in rows 0..3; col 9 in rows 0..1; col 2 in row 0.
    for row, col in [(r, 5) for r in range(4)] + [(r, 9) for r in range(2)] + [(0, 2)]:
        q(ex, "i", f"SetBit(frame=f, rowID={row}, columnID={col})")
    (pairs,) = q(ex, "i", "TopN(frame=f, inverse=true, n=2)")
    assert [(p.id, p.count) for p in pairs] == [(5, 4), (9, 2)]
    # src: columns sharing rows with column 5 (all rows 0..3)
    (pairs,) = q(
        ex, "i",
        "TopN(Bitmap(columnID=5, frame=f), frame=f, inverse=true, n=3)",
    )
    assert [(p.id, p.count) for p in pairs] == [(5, 4), (9, 2), (2, 1)]


def test_topn_folded_matches_two_phase(holder):
    """The folded single-round-trip TopN must return exactly what the
    two-phase protocol returns, across random multi-slice data, with and
    without a src bitmap / n / threshold."""
    import numpy as np

    rng = np.random.default_rng(11)
    c = new_cluster(1)
    e = Executor(holder, host=c.nodes[0].host, cluster=c)
    holder.create_index("i").create_frame("f", cache_size=8)
    bits = []
    for s in range(5):
        base = s * SLICE_WIDTH
        for r in range(20):
            for col in rng.integers(0, 200, rng.integers(1, 40)):
                bits.append((r, base + int(col)))
    must_set_bits(holder, "i", "f", bits)

    # Row 90 exists ONLY in slice 0: a src that is absent from the other
    # slices' fragments exercises the short-circuited TopState branch.
    bits2 = [(90, int(c)) for c in rng.integers(0, 200, 30)]
    must_set_bits(holder, "i", "f", bits2)

    # Row attributes for the filters= shape (even rows tagged "a").
    store = holder.frame("i", "f").row_attr_store
    for r in range(0, 20, 2):
        store.set_attrs(r, {"cat": "a"})

    queries = [
        "TopN(frame=f, n=3)",
        "TopN(frame=f)",
        "TopN(Bitmap(rowID=0, frame=f), frame=f, n=4)",
        "TopN(Bitmap(rowID=1, frame=f), frame=f)",
        "TopN(Bitmap(rowID=2, frame=f), frame=f, n=5, threshold=2)",
        "TopN(Bitmap(rowID=0, frame=f), frame=f, n=3, tanimotoThreshold=20)",
        "TopN(Bitmap(rowID=90, frame=f), frame=f, n=4)",
        'TopN(Bitmap(rowID=0, frame=f), frame=f, n=4, field="cat", filters=["a"])',
        'TopN(frame=f, n=3, field="cat", filters=["a"])',
    ]
    for pql in queries:
        (folded,) = q(e, "i", pql)
        # Force the two-phase protocol by pretending not all local.
        orig = Executor._all_slices_local
        Executor._all_slices_local = lambda self, index, slices: False
        try:
            (two_phase,) = q(e, "i", pql)
        finally:
            Executor._all_slices_local = orig
        assert [(p.id, p.count) for p in folded] == [
            (p.id, p.count) for p in two_phase
        ], pql
        if "filters" in pql:
            # Equivalence alone can't catch filters being silently
            # ignored (both paths share the filter code): assert the
            # semantics directly — only tagged (even) rows may appear.
            assert folded, pql
            assert all(p.id % 2 == 0 for p in folded), (pql, folded)


def test_topn_folded_single_device_fetch(holder, monkeypatch):
    """The folded path issues at most ONE jax.device_get for the whole
    query (the two-phase path needs one per phase)."""
    import jax as _jax

    c = new_cluster(1)
    e = Executor(holder, host=c.nodes[0].host, cluster=c)
    holder.create_index("i").create_frame("f")
    bits = []
    for s in range(4):
        base = s * SLICE_WIDTH
        bits += [(r, base + col) for r in range(6) for col in range(0, 50, r + 1)]
    must_set_bits(holder, "i", "f", bits)

    calls = []
    real = _jax.device_get

    def spy(x):
        calls.append(1)
        return real(x)

    monkeypatch.setattr(_jax, "device_get", spy)
    (pairs,) = q(e, "i", "TopN(Bitmap(rowID=0, frame=f), frame=f, n=3)")
    assert pairs
    assert sum(calls) <= 1, f"folded TopN used {sum(calls)} device fetches"


def test_topn_folded_disjoint_caches_guard(holder):
    """Slices whose ranked caches hold disjoint hot rows: the union
    guard must route to the two-phase protocol (no O(S^2) union scoring)
    and results must stay exact."""
    import numpy as np

    c = new_cluster(1)
    e = Executor(holder, host=c.nodes[0].host, cluster=c)
    holder.create_index("i").create_frame("f", cache_size=600)
    bits = []
    # 4 slices x 600 distinct rows each (rows don't overlap across
    # slices), so the union is ~4x any per-slice candidate list.
    for s in range(4):
        base = s * SLICE_WIDTH
        for r in range(s * 600, (s + 1) * 600):
            bits.append((r, base + (r % 100)))
            if r % 3 == 0:
                bits.append((r, base + 200 + (r % 50)))
    must_set_bits(holder, "i", "f", bits)

    calls = []
    orig = Executor._execute_topn_two_phase

    def spy(self, *a, **k):
        calls.append(1)
        return orig(self, *a, **k)

    Executor._execute_topn_two_phase = spy
    try:
        (pairs,) = q(e, "i", "TopN(frame=f, n=5)")
    finally:
        Executor._execute_topn_two_phase = orig
    assert calls, "union guard did not fall back to two-phase"
    assert len(pairs) == 5
    # every returned count must be exact (2 bits for rows % 3 == 0)
    for p in pairs:
        assert p.count == 2


# ---------------------------------------------------------------------------
# cold-start elimination: persistent compile cache + shape pre-warm
# ---------------------------------------------------------------------------


def test_warmup_prewarm_compiles_standard_shapes():
    from pilosa_tpu.exec import warmup
    from pilosa_tpu.parallel import mesh as pmesh

    n = warmup.prewarm(buckets=(1,))
    per_expr = 2  # count + row at bucket 1
    if pmesh.default_slices_mesh() is not None:
        per_expr += 2 * 2  # mesh chunks (1, 2) x (total-count, row)
    # + the fused TopN scorer's smallest bucket shapes (prewarm_topn:
    # row classes x group classes).
    topn = 2
    assert n == len(warmup._STANDARD_EXPRS) * per_expr + topn


def test_enable_compile_cache_idempotent():
    from pilosa_tpu.exec import warmup

    # The fixed default dir, NOT tmp_path: the cache dir is
    # process-global in JAX, so it must outlive this test or later
    # compiles in the same pytest process would warn on every write.
    d1 = warmup.enable_compile_cache("")
    # Second call (any dir) is a no-op that still reports the active one.
    d2 = warmup.enable_compile_cache("/tmp/pilosa-tpu-other-compile-cache")
    assert d1 is not None and d1 == d2 == warmup.enabled_cache_dir()


class TestCompileCacheDir:
    """Where the persistent cache lives (exec/warmup.py): the directory
    is part of the cache key, so it may never move between runs."""

    @pytest.fixture
    def fresh(self, monkeypatch):
        """An un-enabled warmup module plus a record of every
        ``jax.config.update`` it makes (none reach JAX)."""
        from pilosa_tpu.exec import warmup

        monkeypatch.setattr(warmup, "_enabled_dir", None)
        monkeypatch.delenv(warmup.ENV_CACHE_DIR, raising=False)
        updates = {}
        monkeypatch.setattr(
            jax.config, "update", lambda k, v: updates.__setitem__(k, v)
        )
        return warmup, updates

    def test_env_set_means_no_dir_set_in_code(self, fresh, monkeypatch, tmp_path):
        warmup, updates = fresh
        monkeypatch.setenv(warmup.ENV_CACHE_DIR, str(tmp_path / "from-env"))
        got = warmup.enable_compile_cache(str(tmp_path / "configured"))
        assert got == str(tmp_path / "from-env")
        assert "jax_compilation_cache_dir" not in updates
        # thresholds only
        assert set(updates) == {"jax_persistent_cache_min_compile_time_secs"}
        assert not (tmp_path / "configured").exists()
        # "off" in the config does not undo JAX's own variable either.
        assert warmup.resolve_cache_dir("off") == str(tmp_path / "from-env")

    def test_unset_env_uses_the_fixed_checkout_path(self, fresh, monkeypatch):
        import pilosa_tpu

        warmup, updates = fresh
        monkeypatch.setattr(os, "makedirs", lambda *a, **k: None)
        repo = os.path.dirname(os.path.dirname(pilosa_tpu.__file__))
        want = os.path.join(repo, ".jax-compile-cache")
        assert warmup.DEFAULT_CACHE_DIR == want
        assert warmup.enable_compile_cache("") == want
        assert updates["jax_compilation_cache_dir"] == want

    def test_explicit_config_wins_over_the_default(self, fresh, tmp_path):
        warmup, updates = fresh
        d = str(tmp_path / "explicit")
        assert warmup.enable_compile_cache(d) == d
        assert updates["jax_compilation_cache_dir"] == d
        assert os.path.isdir(d)

    def test_off_disables(self, fresh):
        warmup, updates = fresh
        assert warmup.enable_compile_cache("off") is None
        assert updates == {}

    def test_server_config_no_longer_keys_the_cache_on_the_data_dir(
        self, tmp_path
    ):
        from pilosa_tpu import config as config_mod
        from pilosa_tpu.cli import ctl
        from pilosa_tpu.exec import warmup

        for sub in ("a", "b"):
            cfg = config_mod.load(
                None, environ={}, overrides={"data_dir": str(tmp_path / sub)}
            )
            srv = ctl.build_server(cfg)
            assert (
                warmup.resolve_cache_dir(srv.compilation_cache_dir)
                == warmup.DEFAULT_CACHE_DIR
            )


# ---------------------------------------------------------------------------
# folded-TopN prep cache (per-query validated, like _cached_batch)
# ---------------------------------------------------------------------------


def _topn_fixture(holder, n_slices=3):
    bits = []
    for s in range(n_slices):
        base = s * SLICE_WIDTH
        bits += [(0, base + i) for i in range(6)]
        bits += [(1, base + i) for i in range(4)]
        bits += [(2, base + i) for i in range(2)]
    must_set_bits(holder, "i", "f", bits)


def test_topn_folded_prep_cache_hits_and_stays_exact(ex, holder, monkeypatch):
    _topn_fixture(holder)
    q_text = "TopN(frame=f, n=2)"
    (p1,) = q(ex, "i", q_text)
    builds = []
    real = type(ex)._topn_folded_build

    def spy(self, index, c, slices):
        builds.append(1)
        return real(self, index, c, slices)

    monkeypatch.setattr(type(ex), "_topn_folded_build", spy)
    (p2,) = q(ex, "i", q_text)
    (p3,) = q(ex, "i", q_text)
    assert builds == []  # warm entry: no rebuild
    assert [(p.id, p.count) for p in p2] == [(p.id, p.count) for p in p1]
    assert [(p.id, p.count) for p in p3] == [(p.id, p.count) for p in p1]


def test_topn_folded_cache_adds_no_staleness_beyond_rank_cache(
    ex, holder, monkeypatch
):
    """After writes, the prep-cached executor must answer identically
    to a BRAND-NEW executor over the same holder (the rank cache's
    throttled re-sort is shared state — the prep cache must add no
    staleness of its own)."""
    from pilosa_tpu.cluster.topology import new_cluster
    from pilosa_tpu.exec import Executor as Ex

    _topn_fixture(holder)
    (before,) = q(ex, "i", "TopN(frame=f, n=3)")
    assert [p.id for p in before] == [0, 1, 2]
    for i in range(10, 20):
        q(ex, "i", f"SetBit(frame=f, rowID=2, columnID={SLICE_WIDTH + i})")
    (cached_after,) = q(ex, "i", "TopN(frame=f, n=3)")
    c = new_cluster(1)
    fresh = Ex(holder, host=c.nodes[0].host, cluster=c)
    (fresh_after,) = q(fresh, "i", "TopN(frame=f, n=3)")
    assert [(p.id, p.count) for p in cached_after] == [
        (p.id, p.count) for p in fresh_after
    ]
    # force the throttled re-sort AND expire the prep entry (its
    # lifetime is bounded by the same interval): fresh counts follow
    holder.fragment("i", "f", "standard", 1).cache.recalculate()
    import pilosa_tpu.core.cache as cache_mod

    monkeypatch.setattr(cache_mod, "RECALCULATE_INTERVAL_S", 0.0)
    (forced,) = q(ex, "i", "TopN(frame=f, n=3)")
    counts = {p.id: p.count for p in forced}
    assert counts[2] == 16 and (forced[0].id, forced[0].count) == (0, 18)


def test_topn_score_single_flight_across_queries(ex, holder, monkeypatch):
    """The folded path scores ONCE per validated prep entry: repeated
    (and concurrent) queries of the same TopN shape reuse the fetched
    count vectors instead of re-dispatching the fused scorer — the
    topn.fetch residual ROADMAP 5 names (165 of 171 ms on the CPU
    smoke).  Results must stay byte-identical."""
    _topn_fixture(holder)
    q_text = "TopN(Bitmap(rowID=0, frame=f), frame=f, n=3)"
    (p1,) = q(ex, "i", q_text)  # builds entry + scores
    scored = []
    real = type(ex)._score_topn_parts

    def spy(self, parts):
        scored.append(1)
        return real(self, parts)

    monkeypatch.setattr(type(ex), "_score_topn_parts", spy)
    (p2,) = q(ex, "i", q_text)
    (p3,) = q(ex, "i", q_text)
    assert scored == []  # shared scores: zero re-dispatch, zero fetch
    assert [(p.id, p.count) for p in p2] == [(p.id, p.count) for p in p1]
    assert [(p.id, p.count) for p in p3] == [(p.id, p.count) for p in p1]


def test_topn_score_storm_shares_launches_and_stays_exact(ex, holder):
    """32 concurrent identical TopN queries: far fewer scorer
    dispatches than queries, every answer identical to sequential."""
    import threading

    _topn_fixture(holder)
    q_text = "TopN(Bitmap(rowID=0, frame=f), frame=f, n=3)"
    (want,) = q(ex, "i", q_text)
    want_pairs = [(p.id, p.count) for p in want]

    scored = []
    real = type(ex)._score_topn_parts
    lock = threading.Lock()

    def spy(self, parts):
        with lock:
            scored.append(1)
        return real(self, parts)

    type(ex)._score_topn_parts = spy
    try:
        results = [None] * 32
        errs = []

        def run(k):
            try:
                (r,) = q(ex, "i", q_text)
                results[k] = [(p.id, p.count) for p in r]
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        threads = [
            threading.Thread(target=run, args=(k,)) for k in range(32)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        type(ex)._score_topn_parts = real
    assert not errs
    assert all(r == want_pairs for r in results)
    # Warm entry: the storm shares the already-fetched scores.
    assert len(scored) == 0


def test_topn_score_cache_invalidates_on_write(ex, holder, monkeypatch):
    """A write to a scored fragment rebuilds the entry AND re-scores:
    shared count vectors may never outlive their validity."""
    _topn_fixture(holder)
    q_text = "TopN(frame=f, n=3)"
    (before,) = q(ex, "i", q_text)
    q(ex, "i", f"SetBit(frame=f, rowID=2, columnID={SLICE_WIDTH + 777})")
    scored = []
    real = type(ex)._score_topn_parts

    def spy(self, parts):
        scored.append(1)
        return real(self, parts)

    monkeypatch.setattr(type(ex), "_score_topn_parts", spy)
    (after,) = q(ex, "i", q_text)
    assert scored, "write must force a re-score"
    # No staleness beyond the rank cache's own (documented) throttle:
    # identical to a brand-new executor over the same holder.
    c = new_cluster(1)
    fresh = Executor(holder, host=c.nodes[0].host, cluster=c)
    (fresh_after,) = q(fresh, "i", q_text)
    assert [(p.id, p.count) for p in after] == [
        (p.id, p.count) for p in fresh_after
    ]


def test_topn_folded_cache_invalidates_on_src_frame_write(ex, holder):
    """The src tree's fragments are part of the validity vector: a write
    to the SRC row (same frame here) must re-derive the prep — the
    device-scored counts are exact, so staleness would show directly."""
    _topn_fixture(holder)
    (before,) = q(ex, "i", "TopN(Bitmap(rowID=0, frame=f), frame=f, n=3)")
    c0 = {p.id: p.count for p in before}
    assert c0[1] == 4 * 3  # rows 0/1 overlap on cols 0-3, summed per slice
    assert c0[2] == 2 * 3
    # extend src row 0 AND row 2 with one overlapping new bit in slice 2
    q(ex, "i", f"SetBit(frame=f, rowID=2, columnID={2 * SLICE_WIDTH + 300})")
    q(ex, "i", f"SetBit(frame=f, rowID=0, columnID={2 * SLICE_WIDTH + 300})")
    (after,) = q(ex, "i", "TopN(Bitmap(rowID=0, frame=f), frame=f, n=3)")
    c1 = {p.id: p.count for p in after}
    assert c1[2] == c0[2] + 1


# ---------------------------------------------------------------------------
# the folded build's two ways: a part from the fragment's own layout
# (``direct``) or walked through top_prepare_union_parts (``walked``)
# ---------------------------------------------------------------------------


def _traced(e, pql):
    """``(pairs as tuples, {span name: tags})`` of one traced query."""
    root = e.tracer.start_trace("test")
    with root:
        (pairs,) = q(e, "i", pql)
    rec = e.tracer.finish_root(root)
    return (
        [(p.id, p.count) for p in pairs],
        {s["name"]: s["tags"] for s in rec["spans"]},
    )


@pytest.fixture
def two_ways(holder, monkeypatch):
    """Four slices.  Frames ``f`` and ``o``: rows 0..7 in every slice,
    all dense tier, so every slice ranks the same rows.  ``g``: the
    same, and row 9 in slice 1 alone.  ``s``: the same rows under a
    dense budget of 4, so rows 4..7 live in the sparse tier."""
    import numpy as np

    import pilosa_tpu.core.fragment as fr
    from pilosa_tpu.obs import trace

    rng = np.random.default_rng(33)
    bits = []
    for s in range(4):
        for r in range(8):
            cols = rng.choice(400, size=20 + 10 * r, replace=False)
            bits += [(r, s * SLICE_WIDTH + int(col)) for col in cols]
    idx = holder.create_index("i")
    for name in ("f", "o", "g"):
        idx.create_frame(name)
        must_set_bits(holder, "i", name, bits)
    must_set_bits(
        holder, "i", "g", [(9, SLICE_WIDTH + col) for col in range(0, 300, 3)]
    )
    orig = fr.Fragment.__init__

    def budget_of_four(self, *a, **kw):
        kw.setdefault("dense_row_budget", 4)
        orig(self, *a, **kw)

    idx.create_frame("s")
    monkeypatch.setattr(fr.Fragment, "__init__", budget_of_four)
    must_set_bits(holder, "i", "s", bits)
    monkeypatch.setattr(fr.Fragment, "__init__", orig)
    assert len(holder.fragment("i", "s", "standard", 0)._sparse) == 4
    store = holder.frame("i", "f").row_attr_store
    for r in range(0, 8, 2):
        store.set_attrs(r, {"cat": "a"})
    c = new_cluster(1)
    e = Executor(holder, host=c.nodes[0].host, cluster=c, tracer=trace.Tracer())
    yield e, bits
    e.close()


def _exact(bits, src, ids=None):
    """The exact ranking of ``bits``' rows by overlap with row ``src``."""
    cols: dict[int, set] = {}
    for r, col in bits:
        cols.setdefault(r, set()).add(col)
    pairs = [
        (r, len(cols[r] & cols[src]))
        for r in (ids if ids is not None else cols)
    ]
    return sorted([p for p in pairs if p[1]], key=lambda p: (-p[1], p[0]))


TWO_WAYS = {
    # every slice ranks the union itself, every row dense tier
    "all_dense_equal": ("TopN(Bitmap(frame=f, rowID=3), frame=f, n=8)", "direct"),
    "no_src": ("TopN(frame=f, n=8)", "direct"),
    # row 9 is in the union and in slice 1's rank cache alone
    "foreign_winner": ("TopN(Bitmap(frame=g, rowID=3), frame=g, n=9)", "walked"),
    "sparse_tier_candidate": (
        "TopN(Bitmap(frame=s, rowID=3), frame=s, n=8)", "walked"),
    "threshold": (
        "TopN(Bitmap(frame=f, rowID=2), frame=f, n=5, threshold=5)", "walked"),
    "tanimoto": (
        "TopN(Bitmap(frame=f, rowID=7), frame=f, n=4, tanimotoThreshold=20)",
        "walked"),
    "attr_filter": (
        'TopN(Bitmap(frame=f, rowID=3), frame=f, n=8, field="cat", filters=["a"])',
        "walked"),
    "src_of_another_frame": (
        "TopN(Bitmap(frame=o, rowID=3), frame=f, n=8)", "walked"),
    "src_tree": (
        "TopN(Union(Bitmap(frame=f, rowID=3), Bitmap(frame=f, rowID=4)), "
        "frame=f, n=8)", "walked"),
    # never folded: the per-slice protocol, through the same
    # _top_score_parts
    "ids": ("TopN(Bitmap(frame=f, rowID=3), frame=f, ids=[1, 3, 5, 6])", None),
}


@pytest.mark.parametrize("case", sorted(TWO_WAYS))
def test_topn_folded_ways_match_the_two_phase_and_per_slice_protocols(
    two_ways, monkeypatch, case
):
    """Whichever way each fragment's part was prepared, the folded
    answer is the two-phase protocol's and the per-slice protocol's,
    and ``topn.prep`` says which way the build went."""
    e, bits = two_ways
    pql, build = TWO_WAYS[case]
    got, spans = _traced(e, pql)
    assert got, pql
    call = parse_string(pql).calls[0]
    two_phase = e._execute_topn_two_phase(
        "i", call, [0, 1, 2, 3], ExecOptions(), call.args.get("n", 0)
    )
    assert got == [(p.id, p.count) for p in two_phase]
    monkeypatch.setattr(Executor, "_all_slices_local", lambda *a: False)
    (per_slice,) = q(e, "i", pql)
    assert got == [(p.id, p.count) for p in per_slice]

    if build is None:
        assert "topn.prep" not in spans
    else:
        assert spans["topn.prep"]["prep_cache"] == "built"
        assert spans["topn.prep"]["build"] == build
    # and against the bits themselves where the ranking is exact (every
    # row a candidate in every slice, n no trim)
    if case == "all_dense_equal":
        assert got == _exact(bits, 3)
    elif case == "sparse_tier_candidate":
        assert got == _exact(bits, 3)
    elif case == "ids":
        assert got == _exact(bits, 3, ids=[1, 3, 5, 6])
    elif case == "attr_filter":
        assert got == [p for p in _exact(bits, 3) if p[0] % 2 == 0]
    elif case == "threshold":
        # a threshold cuts slice by slice, so the sums are not _exact's
        assert 1 < len(got) <= 5 and got[0] == _exact(bits, 2)[0]


def test_topn_a_part_without_a_slot_is_scored_beside_parts_read_from_the_plane(
    two_ways, monkeypatch
):
    """One fragment loses its src slot (as after a mirror refresh
    between prepare and attach): it is walked and carries the src's host
    snapshot, the others carry none and are scored from their planes,
    in one answer."""
    from pilosa_tpu.ops import bitplane as bp

    e, bits = two_ways
    # one home device, so the four members meet in the scorer's groups
    monkeypatch.setattr(bp, "home_device", lambda slice_i: jax.devices()[0])
    orig = e._attach_dev_src

    def no_slot_in_slice_2(index, c, frag, part, leaf=None):
        st, sub, srcw, slot = orig(index, c, frag, part, leaf)
        return st, sub, srcw, (None if frag.slice == 2 else slot)

    monkeypatch.setattr(e, "_attach_dev_src", no_slot_in_slice_2)
    built = []
    real = Executor._topn_folded_build

    def keep(self, *a):
        built.append(real(self, *a))
        return built[-1]

    monkeypatch.setattr(Executor, "_topn_folded_build", keep)
    got, spans = _traced(e, "TopN(Bitmap(frame=f, rowID=5), frame=f, n=8)")
    assert got == _exact(bits, 5)
    assert spans["topn.prep"]["build"] == "walked"
    assert spans["topn.dispatch"]["groups"] == 2  # by where the src is read
    # (frag, cand_ids, cand_mask, st, sub_ref, src_words, src_slot)
    words = {p[0].slice: p[5] is not None for p in built[0]["parts"]}
    assert words == {0: False, 1: False, 2: True, 3: False}


def test_topn_second_distinct_text_walks_nothing_and_copies_no_row(
    two_ways, monkeypatch
):
    """On an unchanged index a new text asks the fragments nothing the
    last one did not: no set algebra, no tier split, no 128 KiB copy."""
    import pilosa_tpu.core.fragment as fr

    e, bits = two_ways
    assert _traced(e, "TopN(Bitmap(frame=f, rowID=0), frame=f, n=8)")[0]
    calls = {"top_prepare_union_parts": 0, "_row_words_host": 0,
             "_tier_split_locked": 0}
    for name in calls:
        real = getattr(fr.Fragment, name)

        def spy(self, *a, _name=name, _real=real, **kw):
            calls[_name] += 1
            return _real(self, *a, **kw)

        monkeypatch.setattr(fr.Fragment, name, spy)
    got, spans = _traced(e, "TopN(Bitmap(frame=f, rowID=1), frame=f, n=8)")
    assert got == _exact(bits, 1)
    assert spans["topn.prep"]["build"] == "direct"
    assert calls == {"top_prepare_union_parts": 0, "_row_words_host": 0,
                     "_tier_split_locked": 0}


@pytest.mark.parametrize("write", ["SetBit", "ClearBit", "re-sort"])
def test_topn_layout_is_derived_again_after_a_write_or_a_re_sort(two_ways, write):
    """The fragment's layout lives as long as its version and the rank
    cache's arrays: a write to one fragment, or a re-sort of its rank
    cache, makes the next build derive that fragment's again, and the
    answer is the fresh one."""
    e, bits = two_ways
    frags = [e.holder.fragment("i", "f", "standard", s) for s in range(4)]
    assert _traced(e, "TopN(Bitmap(frame=f, rowID=0), frame=f, n=8)")[0]
    before = [fr_.top_layout() for fr_ in frags]
    assert [fr_.top_layout() for fr_ in frags] == before  # kept, not remade

    row1 = {col for r, col in bits if r == 1}
    row6 = {col for r, col in bits if r == 6}
    in_slice_2 = lambda cols: sorted(  # noqa: E731
        col for col in cols if col // SLICE_WIDTH == 2)
    if write == "SetBit":
        col = in_slice_2(row1 - row6)[0]
        q(e, "i", f"SetBit(frame=f, rowID=6, columnID={col})")
        bits = bits + [(6, col)]
    elif write == "ClearBit":
        col = in_slice_2(row1 & row6)[0]
        q(e, "i", f"ClearBit(frame=f, rowID=6, columnID={col})")
        bits = [b for b in bits if b != (6, col)]
    else:
        frags[2].cache.recalculate()

    got, spans = _traced(e, "TopN(Bitmap(frame=f, rowID=1), frame=f, n=8)")
    assert spans["topn.prep"]["build"] == "direct"
    assert got == _exact(bits, 1)
    after = [fr_.top_layout() for fr_ in frags]
    assert [a is b for a, b in zip(after, before)] == [True, True, False, True]
    # a re-sort that lists the same rows keeps the tier split it had
    assert (after[2].slots is before[2].slots) == (write == "re-sort")
    c = new_cluster(1)
    fresh = Executor(e.holder, host=c.nodes[0].host, cluster=c)
    (same,) = q(fresh, "i", "TopN(Bitmap(frame=f, rowID=1), frame=f, n=8)")
    assert got == [(p.id, p.count) for p in same]
    fresh.close()


def test_topn_a_re_sort_reaches_the_next_text_through_the_layout(two_ways):
    """Cached counts come from the layout: after a forced re-sort the
    next plain TopN ranks by the counts the writes left."""
    e, bits = two_ways
    (before, _) = _traced(e, "TopN(frame=f, n=8)")
    assert before[0][0] == 7  # the fullest row
    for col in range(1000, 1400):
        q(e, "i", f"SetBit(frame=f, rowID=0, columnID={col})")
    frag = e.holder.fragment("i", "f", "standard", 0)
    frag.cache.recalculate()
    after, spans = _traced(e, "TopN(frame=f, n=7)")
    assert spans["topn.prep"]["build"] == "direct"
    assert after[0] == (0, dict(before)[0] + 400)
