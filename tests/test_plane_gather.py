"""The leaf batch of a batch-cache miss, gathered on the device from the
resident plane mirrors (``Executor._assemble_gather_batch``,
``bp.gather_planes``): byte-equal to what the host fills give, one
compiled program whatever the operator and the slice count, read-your-
write, and the host fill wherever the gather does not apply."""

import json

import numpy as np
import pytest

from pilosa_tpu.cluster.topology import new_cluster
from pilosa_tpu.core import fragment as fragment_mod
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.exec import Executor, plan
from pilosa_tpu.exec import executor as executor_mod
from pilosa_tpu.net.client import InternalClient
from pilosa_tpu.net.server import Server
from pilosa_tpu.obs import stats as stats_mod
from pilosa_tpu.ops import bitplane as bp
from pilosa_tpu.parallel import mesh as pmesh
from pilosa_tpu.pql.parser import parse_string
from pilosa_tpu.testing import faults

SW = bp.SLICE_WIDTH
N_SLICES = 191
# slices with no fragment at all, and slices whose fragment holds only a
# row no test asks for: both wholly empty for every tree below
NO_FRAGMENT = (7, 100)
OTHER_ROW_ONLY = (11, 64)

TREES = {
    1: 'Bitmap(frame="f", rowID=1)',
    2: 'Intersect(Bitmap(frame="f", rowID=1), Bitmap(frame="f", rowID=2))',
    # row 99 is held nowhere: a column of zeros
    5: 'Union(Bitmap(frame="f", rowID=1), Bitmap(frame="f", rowID=2),'
       ' Bitmap(frame="f", rowID=3), Bitmap(frame="f", rowID=99),'
       ' Bitmap(frame="f", rowID=5))',
}


def _executor(holder):
    c = new_cluster(1)
    return Executor(holder, host=c.nodes[0].host, cluster=c)


def _stage(holder, frame="f", view="standard", index="i"):
    for frag in holder.view(index, frame, view).fragments():
        frag.device_plane()


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    """191 slices of frame f: row 1 nearly everywhere, rows 2, 3 and 5 in
    fewer and fewer slices, and slice 5 with all eight slots of its plane
    taken, so an absent row has no spare zero slot to point at."""
    h = Holder(str(tmp_path_factory.mktemp("gather") / "data"))
    h.open()
    f = h.create_index_if_not_exists("i").create_frame_if_not_exists("f")
    rng = np.random.default_rng(7)
    for s in range(N_SLICES):
        if s in NO_FRAGMENT:
            continue
        if s in OTHER_ROW_ONLY:
            f.set_bit("standard", 42, s * SW + 1)
            continue
        rows = [r for r, every in ((1, 1), (2, 2), (3, 3), (5, 7)) if s % every == 0]
        if s % 13 == 6:
            rows.remove(1)
        if s == 5:
            rows = [1, 2, 3, 5, 20, 21, 22, 23]
        for r in rows:
            for col in rng.integers(0, SW, 3):
                f.set_bit("standard", r, s * SW + int(col))
        # a column at the slice's end: every plane is of full width, as a
        # filled index's are (a plane is as wide as its columns ask for,
        # and a width class is a program of its own)
        if rows:
            f.set_bit("standard", rows[0], s * SW + SW - 1)
    assert h.fragment("i", "f", "standard", 5).plane_rows() == bp.ROW_BLOCK == 8
    yield h
    h.close()


def _leaves(ex, text):
    call = ex._rewrite_bsi("i", parse_string(text).calls[0])
    return plan.decompose(call)[1]


def _gathered(ex, leaves, slices, mesh=None):
    return ex._assemble_gather_batch(
        leaves, slices, ex._leaf_sweep("i", leaves, slices), mesh
    )


def _host_filled(ex, leaves, slices, mesh=None):
    if mesh is None:
        batch, kept, empties = ex._assemble_host_batch("i", leaves, slices)
        return batch, {s: i for i, s in enumerate(kept)}, kept, empties
    return ex._assemble_mesh_batch_host("i", leaves, slices, mesh)


def _assert_same_batch(got, want):
    assert got is not None, "the gather declined"
    g_batch, g_pos, g_kept, g_empties = got
    w_batch, w_pos, w_kept, w_empties = want
    assert (g_kept, g_empties, g_pos) == (w_kept, w_empties, w_pos)
    if w_batch is None:
        assert g_batch is None
        return
    assert g_batch.shape == w_batch.shape and g_batch.dtype == w_batch.dtype
    np.testing.assert_array_equal(np.asarray(g_batch), np.asarray(w_batch))


@pytest.mark.parametrize("n_slices", [1, 64, 65, N_SLICES])
@pytest.mark.parametrize("n_leaves", sorted(TREES))
def test_the_gathered_batch_is_the_host_fills_batch(
    one_chip, corpus, n_leaves, n_slices
):
    ex = _executor(corpus)
    _stage(corpus)
    leaves = _leaves(ex, TREES[n_leaves])
    assert len(leaves) == n_leaves
    slices = list(range(n_slices))
    want = _host_filled(ex, leaves, slices)
    _assert_same_batch(_gathered(ex, leaves, slices), want)
    if n_slices == N_SLICES:
        assert set(NO_FRAGMENT + OTHER_ROW_ONLY) <= set(want[3])
        assert want[0].shape == (256, n_leaves, bp.WORDS_PER_SLICE)


def test_a_slice_set_with_nothing_set_gathers_nothing(one_chip, corpus):
    ex = _executor(corpus)
    _stage(corpus)
    leaves = _leaves(ex, TREES[2])
    slices = list(NO_FRAGMENT + OTHER_ROW_ONLY)
    got = _gathered(ex, leaves, slices)
    assert got == (None, {}, [], slices)
    _assert_same_batch(got, _host_filled(ex, leaves, slices))


@pytest.mark.parametrize("text", [
    'Range(frame="b", a > 3)',        # planes, five BsiZero pads, a BsiPred
    'Range(frame="b", a >< [2, 6])',  # two predicate rows
    'Intersect(Bitmap(frame="f", rowID=1), Range(frame="b", w < -5))',
])
def test_bsi_planes_zero_pads_and_predicate_rows(one_chip, tmp_path, text):
    h = Holder(str(tmp_path / "data"))
    h.open()
    try:
        idx = h.create_index_if_not_exists("i")
        f = idx.create_frame_if_not_exists("f")
        b = idx.create_frame_if_not_exists("b")
        b.set_options(range_enabled=True)
        b.create_field("a", 0, 7)       # depth 3 in a bucket of 8
        b.create_field("w", -100, 100)
        for s in (0, 1, 3, 4, 6):
            cols = [s * SW + c for c in (3, 9, 200)]
            f.set_bit("standard", 1, cols[0])
            if s != 3:  # slice 3: frame f alone holds a row
                b.import_value("a", cols, [1, 5, 7])
                b.import_value("w", cols, [-50, -5, 60])
        ex = _executor(h)
        for frame, view in (("f", "standard"), ("b", "field_a"), ("b", "field_w")):
            _stage(h, frame, view)
        leaves = _leaves(ex, text)
        names = {leaf.name for leaf in leaves}
        assert "BsiPred" in names and "BsiPlane" in names
        slices = list(range(7))
        _assert_same_batch(
            _gathered(ex, leaves, slices), _host_filled(ex, leaves, slices)
        )
    finally:
        h.close()


def test_planes_of_mixed_shapes_gather_in_runs(one_chip, tmp_path):
    """A fragment with more rows has a larger plane, and the program's key
    holds the shape: members launch together while their planes share one,
    and a run that would pad past the end of the block splits."""
    h = Holder(str(tmp_path / "data"))
    h.open()
    try:
        f = h.create_index_if_not_exists("i").create_frame_if_not_exists("f")
        for s in range(4):
            for r in (1, 2) if s == 0 else range(1, 21):
                f.set_bit("standard", r, s * SW + 10 * r + s)
        shapes = [h.fragment("i", "f", "standard", s).plane_rows() for s in range(4)]
        assert shapes == [8, 32, 32, 32]
        ex = _executor(h)
        _stage(h)
        leaves = _leaves(ex, TREES[2])
        slices = list(range(4))
        _assert_same_batch(
            _gathered(ex, leaves, slices), _host_filled(ex, leaves, slices)
        )
    finally:
        h.close()


@pytest.mark.parametrize("lo,hi,rows,want", [
    (0, 954, 1024, [(0, 954)]),            # the cell: 15 launches of 64
    (0, 239, 256, [(0, 239)]),             # a device's chunk of it
    (0, 3, 4, [(0, 3)]),
    (1, 4, 4, [(1, 3), (3, 4)]),           # 3 members pad to 4: past the end
    (60, 128, 128, [(60, 124), (124, 128)]),
])
def test_no_launch_pads_past_the_end_of_its_block(lo, hi, rows, want):
    got = list(executor_mod._fitting_runs(lo, hi, rows))
    assert got == want
    for a, b in got:
        bucket = bp.score_group_bucket(b - a)
        assert a + -(-(b - a) // bucket) * bucket <= rows


def test_a_tree_over_two_frames_gathers_each_from_its_own_planes(one_chip, tmp_path):
    h = Holder(str(tmp_path / "data"))
    h.open()
    try:
        idx = h.create_index_if_not_exists("i")
        f = idx.create_frame_if_not_exists("f")
        g = idx.create_frame_if_not_exists("g")
        for s in range(6):
            f.set_bit("standard", 1, s * SW + 5)
            if s % 2:
                g.set_bit("standard", 4, s * SW + 5)
        g.set_bit("standard", 4, 9 * SW + 1)  # a slice frame f has not
        ex = _executor(h)
        _stage(h, "f")
        _stage(h, "g")
        text = ('Difference(Bitmap(frame="f", rowID=1), Bitmap(frame="g", rowID=4),'
                ' Bitmap(frame="f", rowID=1))')
        leaves = _leaves(ex, text)
        slices = list(range(10))
        _assert_same_batch(
            _gathered(ex, leaves, slices), _host_filled(ex, leaves, slices)
        )
    finally:
        h.close()


def test_forty_seventy_and_191_fragments_compile_one_gather_program(
    one_chip, corpus
):
    ex = _executor(corpus)
    _stage(corpus)
    plan.clear_program_caches()
    for op in ("Intersect", "Union", "Difference", "Xor"):
        leaves = _leaves(ex, TREES[2].replace("Intersect", op))
        for n in (40, 70, N_SLICES):
            assert _gathered(ex, leaves, list(range(n))) is not None
    assert bp._gather_planes_xla._cache_size() == 1
    # the in-place write is keyed by the block: one a slice bucket here
    # (64 rows is the launch itself)
    assert bp._place_rows_xla._cache_size() == 2
    stats, bounds = plan.program_cache_stats(), plan.program_cache_bounds()
    assert stats["bitplane.gatherPlanes"] == 3 <= bounds["bitplane.gatherPlanes"]


@pytest.fixture
def four_devices():
    bp.configure_mesh_devices(4)
    yield pmesh.default_slices_mesh()
    bp.configure_mesh_devices(0)


@pytest.mark.parametrize("slices", [
    list(range(11)),
    # clustered on device 0: _mesh_placement spills three of its seven
    [0, 4, 8, 12, 16, 20, 24, 1, 2],
    [4, 8, 12, 16, 3],
    [8],
], ids=["spread", "clustered", "clustered5", "one"])
def test_on_a_mesh_the_sharded_batch_is_the_host_fills(
    four_devices, corpus, slices
):
    mesh = four_devices
    assert mesh is not None and mesh.devices.size == 4
    ex = _executor(corpus)
    # the fixture's mirrors may have been staged for another device count
    for frag in corpus.view("i", "f", "standard").fragments():
        frag._invalidate_device()
        frag.device_plane()
    leaves = _leaves(ex, TREES[5])
    got = _gathered(ex, leaves, slices, mesh)
    _assert_same_batch(got, _host_filled(ex, leaves, slices, mesh))
    if len(slices) > 1:
        groups, chunk = ex._mesh_placement(got[2], 4)
        assert got[0].shape[0] == 4 * chunk
        assert len(got[0].sharding.device_set) == 4
    else:
        assert got[0].shape[0] == 1


@pytest.fixture
def assemblers(monkeypatch):
    """Which assembler made each batch of a query."""
    made = []
    for name in ("_assemble_gather_batch", "_assemble_host_batch",
                 "_assemble_mesh_batch_host"):
        def spy(self, *a, _orig=getattr(Executor, name), _name=name, **kw):
            out = _orig(self, *a, **kw)
            made.append(_name if out is not None else _name + ":declined")
            return out
        monkeypatch.setattr(Executor, name, spy)
    return made


def _small_index(h, slices=6):
    f = h.create_index_if_not_exists("i").create_frame_if_not_exists("f")
    for s in range(slices):
        for r in (1, 2):
            f.set_bit("standard", r, s * SW + 5)
        f.set_bit("standard", 1, s * SW + 9)
    return f


@pytest.fixture
def holder(tmp_path):
    h = Holder(str(tmp_path / "data"))
    h.open()
    yield h
    h.close()


def _count(ex, text):
    return ex.execute("i", parse_string(f"Count({text})"))[0]


def test_an_acknowledged_setbit_is_in_the_gathered_row(one_chip, holder, assemblers):
    _small_index(holder)
    ex = _executor(holder)
    _stage(holder)
    assert _count(ex, TREES[2]) == 6
    ex.execute("i", parse_string(f'SetBit(frame="f", rowID=2, columnID={3 * SW + 9})'))
    frag = holder.fragment("i", "f", "standard", 3)
    # the write is queued against the mirror, not uploaded
    assert frag._device is not None and frag._device_pending
    assert _count(ex, TREES[2]) == 7
    assert not frag._device_pending
    assert assemblers == ["_assemble_gather_batch"] * 2


def test_a_sparse_tier_row_fills_on_the_host(one_chip, holder, assemblers, monkeypatch):
    orig = fragment_mod.Fragment.__init__

    def zero_budget(self, *a, **kw):
        kw.setdefault("dense_row_budget", 0)
        orig(self, *a, **kw)

    monkeypatch.setattr(fragment_mod.Fragment, "__init__", zero_budget)
    _small_index(holder)
    assert holder.fragment("i", "f", "standard", 0).holds_sparse_tier_rows()
    ex = _executor(holder)
    _stage(holder)
    # (a Union: the anchored pre-pass answers an Intersect of such rows)
    assert _count(ex, TREES[2].replace("Intersect", "Union")) == 12
    assert assemblers == ["_assemble_gather_batch:declined", "_assemble_host_batch"]


def test_a_mostly_cold_set_fills_on_the_host(one_chip, holder, assemblers):
    _small_index(holder)
    ex = _executor(holder)
    frags = holder.view("i", "f", "standard").fragments()
    for frag in frags[:2]:
        frag.device_plane()
    assert _count(ex, TREES[2]) == 6
    assert assemblers == ["_assemble_host_batch"]
    # half of them resident: the gather, which uploads the rest on its way
    frags[2].device_plane()
    assert _count(ex, TREES[2].replace("Intersect", "Union")) == 12
    assert assemblers[1:] == ["_assemble_gather_batch"]
    assert all(f._device is not None for f in frags)


def test_a_device_fault_under_the_gather_fills_on_the_host(
    one_chip, holder, assemblers
):
    _small_index(holder)
    ex = _executor(holder)
    _stage(holder)
    try:
        # PILOSA_FAULTS=device.launch:... as a process would be given it
        faults.install("device.launch:kind=oom,path=gather")
        assert _count(ex, TREES[2]) == 6
        assert _count(ex, TREES[2].replace("Intersect", "Xor")) == 6
    finally:
        faults.clear()
    assert assemblers == [
        "_assemble_gather_batch:declined", "_assemble_host_batch"] * 2
    assert not ex.device_health.degraded()
    assert _count(ex, TREES[2].replace("Intersect", "Union")) == 12
    assert assemblers[4:] == ["_assemble_gather_batch"]


def test_a_fault_at_every_launch_site_still_answers_the_same(
    one_chip, holder, assemblers
):
    _small_index(holder)
    ex = _executor(holder)
    _stage(holder)
    try:
        faults.install("device.launch:kind=error")
        assert _count(ex, TREES[2]) == 6
    finally:
        faults.clear()
    assert assemblers[:2] == [
        "_assemble_gather_batch:declined", "_assemble_host_batch"]


OPERATORS = {
    "Intersect": lambda a, b: a & b,
    "Union": lambda a, b: a | b,
    "Difference": lambda a, b: a - b,
    "Xor": lambda a, b: a ^ b,
}


def test_served_counts_of_all_four_operators_at_seventy_slices(one_chip, tmp_path):
    s = Server(
        data_dir=str(tmp_path / "data"),
        stats=stats_mod.ExpvarStatsClient(),
        anti_entropy_interval=3600,
        polling_interval=3600,
        cache_flush_interval=3600,
    )
    s.open()
    try:
        f = s.holder.create_index_if_not_exists("i").create_frame_if_not_exists("f")
        rng = np.random.default_rng(3)
        cols = {r: set() for r in (1, 2, 3)}
        for r, per_slice in ((1, 40), (2, 25), (3, 5)):
            for sl in range(70):
                if r == 3 and sl % 4:
                    continue
                for c in rng.integers(0, 64, per_slice):  # a few columns: overlap
                    cols[r].add(sl * SW + int(c))
        for r, cs in cols.items():
            for col in sorted(cs):
                f.set_bit("standard", r, col)
        _stage(s.holder)
        c = InternalClient(s.host, timeout=120.0)
        for op, fn in OPERATORS.items():
            for a, b in ((1, 2), (2, 3), (3, 1)):
                text = (f'Count({op}(Bitmap(frame="f", rowID={a}),'
                        f' Bitmap(frame="f", rowID={b})))')
                assert c.execute_pql("i", text) == len(fn(cols[a], cols[b])), text
                _status, data = c._request("GET", "/debug/traces")
                spans = json.loads(data)["traces"][-1]["spans"]
                leaves = next(x for x in spans if x["name"] == "plan.leaves")
                assert leaves["tags"]["path"] == "plane_gather"
                assert leaves["tags"]["launches"] == 2
    finally:
        s.close()
