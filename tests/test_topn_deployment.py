"""The deployment ``segment-1b-topn`` at a size a test holds: the served
``TopN(src)`` and plain ``TopN`` against the deployment kind's own plain
reference, the scorer whose program is bounded in operands, the one
accounting of a plane, and a rehearsal of the cell through the
benchmark's ``run_cell``.  Everything here runs on the CPU; what the
cell does on the chip only a chip run can say (``PERF.md``)."""

import copy
import json
import os
import re
import sys

import numpy as np
import pytest

from pilosa_tpu import device as device_mod
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.exec import plan, topn_stack
from pilosa_tpu.exec.executor import Executor
from pilosa_tpu.net.client import InternalClient
from pilosa_tpu.net.server import Server
from pilosa_tpu.obs import stats as stats_mod
from pilosa_tpu.ops import bitplane as bp
from pilosa_tpu.pql.parser import parse_string

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import control  # noqa: E402 — benchmarks/control.py
import run  # noqa: E402 — benchmarks/run.py

CELL = "segment-1b.topn-src"
G = bp.SCORE_GROUP


def tiny_config(slices=3, rows=12) -> dict:
    """The shipped configuration cut to a test's size: its kind, schema,
    frame options and keys as they are."""
    cfg = run.read_json(os.path.join(BENCH, "configs", "segment-1b-topn.json"))
    cfg.update(slices=slices, rows=rows, columns=slices * cfg["slice_width"],
               density={"head": [0.02, 0.01], "base": 0.008, "decay": 0.8,
                        "floor": 0.002})
    return cfg


@pytest.fixture(scope="module")
def kind():
    return run.load_kind("topn-src")


# ---------------------------------------------------------------------------
# (a) the served path against the kind's reference
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module", params=[11, 2_900_000_017])
def served(request, kind, tmp_path_factory):
    """A server with the kind's schema and the seed's data, loaded as
    the harness loads it, and the reference that made the data."""
    cfg = tiny_config()
    ref = kind.Reference(cfg, request.param)
    s = Server(
        data_dir=str(tmp_path_factory.mktemp("topn") / "data"),
        stats=stats_mod.ExpvarStatsClient(),
        anti_entropy_interval=3600, polling_interval=3600,
        cache_flush_interval=3600,
    )
    s.open()
    try:
        c = InternalClient(s.host, timeout=120.0)
        for index in kind.schema(cfg):
            c.create_index(index["name"], index.get("options"))
            for frame in index["frames"]:
                c.create_frame(index["name"], frame["name"], frame.get("options"))
        for unit in ref.units():
            u = ref.make(unit)
            c.import_bits(u["index"], u["frame"], u["slice"], (u["rows"], u["cols"]))
        ref.seal()
        yield c, ref, cfg
    finally:
        s.close()


# src rows: the two fullest (2 % and 1 %), a thin one, the thinnest, one
# the index does not hold, and none (the plain TopN); n at least the rows.
@pytest.mark.parametrize("src", [0, 1, 5, 11, 40, None])
@pytest.mark.parametrize("n", [12, 100])
def test_the_served_topn_is_the_references_exact_ranking(served, kind, src, n):
    c, ref, cfg = served
    text = (f"TopN(frame={cfg['frame']}, n={n})" if src is None else
            f"TopN(Bitmap(frame={cfg['frame']}, rowID={src}), "
            f"frame={cfg['frame']}, n={n})")
    # as the benchmark's client asks: JSON over POST /index/<i>/query
    status, data = c._request("POST", f"/index/{cfg['index']}/query",
                                body=text.encode())
    assert status == 200
    got = kind.normalise(json.loads(data)["results"][0])
    want = ref.answer(("TopN", src, n))
    assert got == want
    if src == 40:
        assert want == []
    else:
        assert len(want) == cfg["rows"] and want[0][0] == (0 if src is None else src)
        counts = [cnt for _, cnt in want]
        assert counts == sorted(counts, reverse=True) and counts[-1] > 0
    # the broken references of the control differ from it here too
    assert all(ref.answer(("TopN", src, n), broken=b) != want or not want
               for b in kind.CONTROLS)


# ---------------------------------------------------------------------------
# (b) the bucketed scorer
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def many_slices(tmp_path_factory):
    """3G - 1 fragments of a few rows each, row 1 the src."""
    holder = Holder(str(tmp_path_factory.mktemp("many")))
    holder.open()
    f = holder.create_index("i").create_frame("f", cache_size=512)
    n = 3 * G - 1
    rng = np.random.default_rng(5)
    rows = np.repeat(np.arange(1, 6), 40)
    for s in range(n):
        cols = rng.integers(0, 4096, size=rows.size) + s * bp.SLICE_WIDTH
        f.import_bulk(rows, cols)
    yield holder, n
    holder.close()


def _score_parts(ex, text, slices):
    """The scorer's inputs for ``slices``, as the executor's own map
    step builds them."""
    c = parse_string(text).calls[0]
    src_rows = ex._eval_tree_slices_host("i", c.children[0], slices)
    prepped = [ex._prepare_topn_slice("i", c, s, src_rows=src_rows) for s in slices]
    return [(*ex._attach_dev_src("i", c, frag, part), frag) for frag, part in prepped]


@pytest.mark.parametrize("members", [1, G, G + 1, 3 * G - 1])
def test_the_bucketed_scorer_gives_hostevals_vectors(one_chip, many_slices, members):
    holder, _n = many_slices
    ex = Executor(holder)
    try:
        parts = _score_parts(ex, "TopN(Bitmap(frame=f, rowID=1), frame=f, n=10)",
                             list(range(members)))
        assert all(p[3] is not None for p in parts)  # src read from the plane
        stack = topn_stack.score_stack(parts)
        assert [g.slots.shape for g in stack.groups] == [(members, len(parts[0][1].slots))]
        assert stack.groups[0].slots.flags.c_contiguous
        stack.hand_out(parts, ex._score_topn_parts(stack))
        device = [np.array(p[0].counts[: len(p[0].dense_pos)]) for p in parts]
        assert len(device) == members and any(v.any() for v in device)
        for p in parts:
            p[0].counts = None
        ex.hosteval.score_topn_parts(parts)
        for got, p in zip(device, parts):
            np.testing.assert_array_equal(got, p[0].counts)
    finally:
        ex.close()


def test_the_number_of_scorer_programs_does_not_depend_on_the_slice_count(
    one_chip, many_slices
):
    holder, n = many_slices
    ex = Executor(holder)
    try:
        plan.clear_program_caches()
        text = "TopN(Bitmap(frame=f, rowID=1), frame=f, n=10)"
        seen = []
        for members in (G - 24, G + 6, n):  # 40, 70 and 191 fragments at G = 64
            ex._score_topn_parts(
                topn_stack.score_stack(_score_parts(ex, text, list(range(members))))
            )
            seen.append(plan.program_cache_stats()["bitplane.scorePlanes"])
        assert seen == [1, 1, 1]
        assert bp.shape_highwater()["score_frags"] == G
        bounds = plan.program_cache_bounds()["bitplane.scorePlanes"]
        assert 1 <= bounds <= 2 * bp.bucket_classes(G)
        # and the served answer over all of them is the plain arithmetic
        (pairs,) = ex.execute("i", parse_string(text))
        frame = holder.index("i").frame("f")
        want = {}
        for s in range(n):
            frag = frame.view("standard").fragment(s)
            src = frag._row_words_host(1)
            for r in range(1, 6):
                want[r] = want.get(r, 0) + int(
                    np.bitwise_count(frag._row_words_host(r) & src).sum())
        assert [(p.id, p.count) for p in pairs] == sorted(
            want.items(), key=lambda p: (-p[1], p[0]))
        assert plan.program_cache_stats()["bitplane.scorePlanes"] == 1
    finally:
        ex.close()


def test_prewarm_warms_the_programs_the_holders_indexes_will_use(one_chip, many_slices):
    from pilosa_tpu.exec import warmup

    holder, n = many_slices
    assert warmup.topn_shapes(holder) == [(G, bp.ROW_BLOCK, bp.MIN_ROW_WORDS)]
    plan.clear_program_caches()
    assert warmup.prewarm_topn(warmup.topn_shapes(holder)) == 1
    assert plan.program_cache_stats()["bitplane.scorePlanes"] == 1
    assert plan.program_cache_compile_ms()["topn.score"] > 0
    ex = Executor(holder)
    try:
        ex.execute("i", parse_string("TopN(Bitmap(frame=f, rowID=2), frame=f, n=10)"))
    finally:
        ex.close()
    assert plan.program_cache_stats()["bitplane.scorePlanes"] == 1


def test_prewarm_takes_no_shape_from_a_bsi_fields_bit_planes(one_chip, tmp_path):
    """TopN never ranks a field's bit planes: a program warmed for them
    is one no query uses, and a restart would compile what the first
    boot never had (``chip_smoke.py`` holds a restart to that)."""
    from pilosa_tpu.exec import warmup

    holder = Holder(str(tmp_path))
    holder.open()
    try:
        idx = holder.create_index("i")
        f = idx.create_frame("f", cache_size=512)
        v = idx.create_frame("v")
        v.set_options(range_enabled=True)
        v.create_field("q", 0, 1000)
        for s in range(3):
            f.set_bit("standard", 1, s * bp.SLICE_WIDTH + 3)
            v.import_value("q", [s * bp.SLICE_WIDTH + 3], [7 * s + 1])
        assert any(n.startswith("field_") for n in v.views())
        assert warmup.topn_shapes(holder) == [(4, bp.ROW_BLOCK, bp.MIN_ROW_WORDS)]
    finally:
        holder.close()


@pytest.fixture(scope="module")
def one_v5e_chip():
    """A TPU v5e that is described and not attached: the chip's own
    compiler runs here, nothing executes (the ``on-chip-measurement``
    guide, section 2).  Made inside a fixture of this one file: only the
    worker that is given the file loads the TPU's library."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler here: nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_scorer_compiles_for_the_chip_at_the_cells_own_width(one_v5e_chip):
    """The program the cell's every TopN(src) launches: SCORE_GROUP plane
    mirrors of 64 rows x 32,768 words, their slots and src slots.  A
    compile that passes is not a chip run and says nothing of its speed."""
    import jax
    import jax.numpy as jnp

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_v5e_chip)

    planes = tuple(shape((64, bp.WORDS_PER_SLICE), jnp.uint32) for _ in range(G))
    scorer = bp.self_src_scorer("tpu", (64, bp.WORDS_PER_SLICE), 64)
    assert scorer is bp._score_planes_kernel
    lowered = scorer.lower(planes, shape((G, 64), jnp.int32), shape((G,), jnp.int32))
    compiled = lowered.compile()
    # What a traced run's profile holds of each launch: the kernel and
    # the pick of the candidates' counts, a handful of operations where
    # the fused XLA program has ~18 a member (1,141 a launch)
    entry = compiled.as_text().split("\nENTRY ", 1)[1].split("\n}", 1)[0]
    ops = re.findall(r"\s([a-z][a-z-]*)\((?:%|\))", entry)
    executed = [op for op in ops if op not in (
        "parameter", "constant", "get-tuple-element", "bitcast", "tuple")]
    assert ops.count("custom-call") == 1 and len(executed) <= 8
    mem = compiled.memory_analysis()
    mirrors = G * 64 * bp.WORDS_PER_SLICE * 4
    # the operands are the mirrors themselves and two small index arrays
    assert mirrors < mem.argument_size_in_bytes < mirrors + (1 << 17)
    assert G * 64 * 4 <= mem.output_size_in_bytes < (1 << 17)
    # no stacked copy of the planes: the scratch is a fraction of one launch's planes
    assert mem.temp_size_in_bytes < mem.argument_size_in_bytes // 8


def test_the_leaf_batch_gather_compiles_for_the_chip_at_the_cells_own_width(
    one_v5e_chip,
):
    """What a Count that misses the batch cache launches in
    ``segment-1b.count-distinct``: the gather of two rows from each of
    SCORE_GROUP plane mirrors, and the write of a launch's output into the
    1024-row block, in place.  (Here beside the scorer's: the tests that
    describe the chip stay in one file.)"""
    import jax
    import jax.numpy as jnp

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_v5e_chip)

    row = bp.WORDS_PER_SLICE * 4
    planes = tuple(shape((64, bp.WORDS_PER_SLICE), jnp.uint32) for _ in range(G))
    mem = bp._gather_planes_xla.lower(
        planes, shape((bp.GATHER_TABLE, G, 2), jnp.int32), shape((), jnp.int32)
    ).compile().memory_analysis()
    assert mem.output_size_in_bytes == G * 2 * row
    # the mirrors are operands, read where they lie: no stacked copy
    assert mem.temp_size_in_bytes < G * 2 * row

    block = 1024 * 2 * row
    mem = bp._place_rows_xla.lower(
        shape((1024, 2, bp.WORDS_PER_SLICE), jnp.uint32),
        shape((G, 2, bp.WORDS_PER_SLICE), jnp.uint32),
        shape((), jnp.int32), shape((), jnp.int32),
    ).compile().memory_analysis()
    # the donated block is the output: no second 256 MiB
    assert mem.alias_size_in_bytes == block == mem.output_size_in_bytes
    assert mem.temp_size_in_bytes < G * 2 * row


def test_the_walked_scorer_compiles_for_the_chip_at_the_cells_own_width(one_v5e_chip):
    """What every answer of ``chembl-tanimoto.screen`` launches:
    ``bp.score_rows`` over the 2^21-row plane of 128-word rows and the
    cached counts beside it.  The one loop of the program is the
    hand-back's, a step of ``bp.ROW_STEP`` kept rows a trip: a search
    by rounds of probes inside it would be a second.  (Here beside the
    scorer's: the tests that describe the chip stay in one file.)"""
    import jax
    import jax.numpy as jnp

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_v5e_chip)

    rows, words = 1 << 21, 128
    compiled = bp._score_rows_xla.lower(
        shape((rows, words), jnp.uint32), shape((rows,), jnp.int32),
        shape((4,), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    # the plane is read where it lies; out come the count, ROW_HITS
    # (slot, shared) pairs and the masked vector
    assert mem.argument_size_in_bytes < rows * words * 4 + rows * 4 + (1 << 16)
    assert mem.output_size_in_bytes < rows * 4 + 2 * bp.ROW_HITS * 4 + (1 << 16)
    assert compiled.as_text().count(" while(") == 1


def test_the_in_place_aggregate_compiles_for_the_chip_at_the_cells_own_width(
    one_v5e_chip, tmp_path
):
    """What every answer of ``ssb-sf100-q1.sum-drill`` launches: SSB's
    Q1.1 as the executor lays it out over the deployment's own fields (a
    27-bit ``lo_discounted`` in a 32-row plane, ``lo_discount``,
    ``lo_quantity`` and ``d_year`` in 8-row planes), ``bp.AGG_GROUP``
    slices a launch, the plane mirrors as operands, the DMA stage as the
    chip's own kernel compiler takes it.  (Here beside the scorer's: the
    tests that describe the chip stay in one file.)"""
    import jax
    import jax.numpy as jnp

    from pilosa_tpu.obs import trace

    cfg = run.read_json(os.path.join(BENCH, "configs", "ssb-sf100-q1.json"))
    mix = run.read_json(os.path.join(BENCH, "traffic", "sum-drill.json"))
    holder = Holder(str(tmp_path))
    holder.open()
    try:
        idx = holder.create_index("ssb")
        lo = idx.create_frame("lo")
        lo.set_options(range_enabled=True)
        for name, (low, high) in cfg["measures"]["fields"].items():
            lo.create_field(name, low, high)
        # columns over the whole slice, so the planes are of the cell's width
        cols = np.arange(27) * (bp.SLICE_WIDTH // 27)
        lo.import_value("lo_discounted", cols, 1 << np.arange(27))  # every magnitude plane
        lo.import_value("lo_discount", cols, np.arange(27) % 11)
        lo.import_value("lo_quantity", cols, np.arange(27) * 2 % 50 + 1)
        idx.create_frame("d_year").import_bulk(np.full(27, 1993), cols)
        holder.warm_device_mirrors()  # cold ones would take the leaf batch
        ex = Executor(holder)
        try:
            text = mix["read"]["templates"]["q1.1"].format(year=1993, dlo=1, dhi=3, k=25)
            rc = ex._rewrite_bsi_agg("ssb", parse_string(text).calls[0])
            with trace.NOP_TRACER.span("bsi.prep") as sp:
                prep = ex._agg_in_place_prep("ssb", rc, [0], sp)
        finally:
            ex.close()
    finally:
        holder.close()
    ((_, planes, (slots,)),) = prep["groups"]
    assert [int(p.shape[0]) for p in planes] == [32, 8, 8, 8]
    # the rows an answer reads in a slice, as the benchmark's reducer counts them
    assert (slots >= 0).sum() == prep["planes"] == cfg["planes_read"]["q1.1"] == 41
    # the pads to the depth bucket are the program's zeros (the schema says
    # them); a sign row no column ever set is a slot not held: data
    assert sum(c[0] == "zero" for c in prep["cols"]) == 5 + 4 + 2
    assert (slots < 0).sum() == 3
    assert len(prep["preds"]) == 3
    # the fields' planes are copied whole; of d_year's plane the tile
    # around the one row
    assert prep["units"] == ("whole", "tile", "whole", "whole")

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_v5e_chip)

    # Q1.1: 56 rows copied and 44 picked a member, 100 MiB a launch of 8:
    # what stays in the core's fast memory; Q1.2, Q1.3: 64 and 45 rows
    g = bp.agg_members(573, 32 + bp.TILE_ROWS + 8 + 8 + slots.size)
    assert g == 8 == bp.agg_members(573, 64 + 45)
    operands = tuple(shape(p.shape, jnp.uint32) for _ in range(g) for p in planes)
    compiled = bp._aggregate_planes_xla.lower(
        plan._eval_expr, prep["expr"], prep["cols"], prep["units"], False,
        operands, shape((bp.GATHER_TABLE, g, slots.size), jnp.int32),
        shape((), jnp.int32), shape(prep["preds"].shape, jnp.uint32),
    ).compile()
    mem = compiled.memory_analysis()
    mirrors = g * (32 + 8 + 8 + 8) * bp.WORDS_PER_SLICE * 4
    # the operands are the mirrors themselves, a slot table and the predicates
    assert mirrors < mem.argument_size_in_bytes < mirrors + (1 << 18)
    # a 65-word vector a slice comes back (padded to the chip's tiles)
    assert g * (2 * 32 + 1) * 4 <= mem.output_size_in_bytes <= g * 128 * 4
    # the block of a launch and the rows picked out of it live in the
    # core's fast memory: next to nothing of them in HBM, let alone a leaf
    # batch of 58 rows a padded slice
    assert 0 < mem.temp_size_in_bytes < 4 * g * bp.WORDS_PER_SLICE * 4
    # a launch is a hundred-odd device operations (a gather a leaf made it
    # 412, and a profile of the cell's 10 s could not be read: PERF.md §6)
    entry = re.search(r"ENTRY [^{]*\{(.*?)\n\}", compiled.as_text(), re.S).group(1)
    ops = [m.group(1) for m in re.finditer(r"= \S+ ([\w-]+)\(", entry)]
    idle = {"parameter", "get-tuple-element", "bitcast", "tuple", "constant"}
    assert 50 < sum(op not in idle for op in ops) < 150


@pytest.mark.parametrize("leaves,ops", [(4, 8), (8, 16)])
def test_the_fused_interpreters_scratch_is_what_its_budget_counts(
    one_v5e_chip, leaves, ops
):
    """``coalesce.FUSE_SCRATCH_FACTOR`` register files bound the temp the
    chip's compiler allots the interpreter at the cells' 1024 batch rows:
    counted as one, launches of 5-8 GiB passed a 2 GiB budget."""
    import jax
    import jax.numpy as jnp

    from pilosa_tpu.exec import coalesce

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_v5e_chip)

    mem = plan._compiled_interp("count").fn.lower(
        shape((1024, leaves, bp.WORDS_PER_SLICE), jnp.uint32),
        shape((ops, 4), jnp.int32), shape((4,), jnp.int32),
    ).compile().memory_analysis()
    register_file = 1024 * (leaves + ops) * bp.WORDS_PER_SLICE * 4
    counted = coalesce.FUSE_SCRATCH_FACTOR * register_file
    assert register_file < mem.temp_size_in_bytes <= counted
    # so at this size nothing fuses on one chip; a quarter of the rows (a
    # device's share on four) does while the program is small
    assert counted > coalesce.MAX_FUSE_BYTES
    assert (counted / 4 < coalesce.MAX_FUSE_BYTES) == (leaves + ops == 12)


# ---------------------------------------------------------------------------
# (c) one accounting of a plane
# ---------------------------------------------------------------------------


def test_a_prep_entry_is_not_charged_for_mirrors_the_pool_already_holds(tmp_path):
    holder = Holder(str(tmp_path))
    holder.open()
    f = holder.create_index("i").create_frame("f", cache_size=512)
    for s in range(6):
        for r in (1, 2, 3):
            f.set_bit("standard", r, s * bp.SLICE_WIDTH + r)
            f.set_bit("standard", r, s * bp.SLICE_WIDTH + 7)
    ex = Executor(holder)
    pool = device_mod.pool()
    try:
        q = parse_string("TopN(Bitmap(frame=f, rowID=1), frame=f, n=10)")
        before = pool.snapshot()
        (pairs,) = ex.execute("i", q)
        assert [(p.id, p.count) for p in pairs] == [(1, 12), (2, 6), (3, 6)]
        after = pool.snapshot()
        frags = [f.view("standard").fragment(s) for s in range(6)]
        mirrors = sum(int(fr._plane.nbytes) for fr in frags)

        grew = {k: after[k] - before[k] for k in ("resident_bytes", "cache_bytes")}
        # the six mirrors went in once, under the fragments' own keys, and
        # the prep entry that points at them added nothing
        assert grew == {"resident_bytes": mirrors, "cache_bytes": 0}
        assert len(ex._topn_cache) == 1
        assert all(fr.mirror_is(p[4].plane)
                   for fr, p in zip(frags, next(iter(ex._topn_cache.values()))["parts"]))
        # a snapshot that a write has replaced is the entry's own to carry
        key = next(iter(ex._topn_cache))
        ent = ex._topn_cache[key]
        f.set_bit("standard", 2, 9)
        frags[0].device_plane()  # the refresh: a new mirror array
        assert not frags[0].mirror_is(ent["parts"][0][4].plane)
    finally:
        ex.close()
        holder.close()


# ---------------------------------------------------------------------------
# (d) the cell through the benchmark's own run_cell
# ---------------------------------------------------------------------------

# Per-layer metrics listed for the cell that only a chip run can read.
DEVICE_ONLY = {"device.idle_share", "device.topn_roofline",
               "device.hbm_in_use_bytes", "device.hbm_peak_bytes"}


@pytest.fixture(scope="module")
def tiny_bench(tmp_path_factory):
    """``BENCHMARK.json`` with the cell's configuration cut to 4 slices x
    12 rows; the kind, the mix and the metrics' files are the shipped ones."""
    bench = copy.deepcopy(run.read_json(os.path.join(REPO, "BENCHMARK.json")))
    path = tmp_path_factory.mktemp("cfg") / "segment-tiny-topn.json"
    path.write_text(json.dumps(tiny_config(slices=4)))
    next(c for c in bench["configs"] if c["name"] == "segment-1b-topn")["file"] = str(path)
    return bench


@pytest.fixture
def in_a_test_process(monkeypatch):
    """``run_cell`` refuses a caller that has initialised a JAX backend,
    because on the chip's machine that caller would hold the chip.  This
    process has (every test here uses the CPU backend), and the server
    child is held to the CPU by its environment."""
    monkeypatch.setattr(run, "jax_backend_in_this_process", lambda: False)


def _rig(server_argv=None):
    env = {"JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
           "PILOSA_TPU_COMPILATION_CACHE_DIR": "off"}
    return run.Rig(platform="cpu", server_argv=server_argv, extra_env=env)


def test_the_cells_files_are_found_by_name(tiny_bench, kind):
    cell = run.Cell(tiny_bench, CELL, _rig())
    assert cell.kind.__file__ == kind.__file__ and cell.chips == 1
    assert cell.kind.SITES == ("topn",)
    assert cell.kind.schema(cell.config) == [{"name": "segment", "frames": [
        {"name": "f", "options": {"cacheType": "ranked", "cacheSize": 50000}}]}]
    traffic = cell.kind.Traffic(cell.mix, cell.config, 1)
    texts = [traffic.read(i // 8, i % 8).key for i in range(68 * 2)]
    # dealt in turn: a text returns after 68 requests, every src once a lap
    assert texts[:68] == texts[68:]
    assert sorted(t[1] for t in texts[:68] if t[1] is not None) == list(range(64))
    assert [i for i, t in enumerate(texts[:68]) if t[1] is None] == [0, 17, 34, 51]
    assert {t[2] for t in texts} == {100}
    assert traffic.read(0, 1).text == "TopN(Bitmap(frame=f, rowID=0), frame=f, n=100)"
    assert traffic.read(0, 0).text == "TopN(frame=f, n=100)"
    warm = traffic.warmup_rounds()
    assert [len(r) for r in warm] == [8, 8] and warm[0][0].key[1] is None
    listed = {m["name"] for m in cell.per_layer}
    assert {"exec.topn_prep_ms", "exec.topn_select_ms", "device.topn_dispatch_ms",
            "device.topn_fetch_ms", "exec.topn_scored_share",
            "exec.topn_select_stacked_share", "device.topn_roofline",
            "exec.topn_prep_kept_share"} <= listed
    assert not {"exec.plan_ms", "exec.map_local_self_ms", "device.count_roofline"} & listed


def test_a_program_without_the_bounded_scorer_is_refused_before_a_server_boots(
    kind, tmp_path
):
    """The configuration names what it needs of the program, and the kind
    reads it as text: the parent commit, whose scorer takes one operand a
    slice, fails at once and never boots a server."""
    cfg = run.read_json(os.path.join(BENCH, "configs", "segment-1b-topn.json"))
    assert [n["text"] for n in cfg["needs"]] == ["SCORE_GROUP"]
    kind.program_can_serve(cfg)  # this tree
    old = tmp_path / "pilosa_tpu" / "ops"
    old.mkdir(parents=True)
    (old / "bitplane.py").write_text("def score_planes(planes, slots): ...\n")
    with pytest.raises(run.HarnessError, match="cannot serve 'segment-1b-topn'"):
        kind.program_can_serve(cfg, root=str(tmp_path))
    with pytest.raises(run.HarnessError, match="lacks 'SCORE_GROUP'"):
        kind.program_can_serve(cfg, root=str(tmp_path / "nowhere"))


def test_a_traced_rehearsal_is_correct_and_reads_every_listed_metric(
    in_a_test_process, tiny_bench
):
    # ROADMAP D20: a short window, so that the rehearsal's requests stay
    # under one deal of the 68 texts where the machine allows it (a CPU
    # under six test workers gets through 8 to a few hundred)
    rc, line = run.run_cell(tiny_bench, CELL, 2_900_000_033, 0.3, True, _rig())
    assert rc == 0
    line = json.loads(json.dumps(line))
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 8
    c = line["compared"]
    assert c["wrong_answers"] == {"value": 0, "limit": 0}
    assert c["hosteval_launches"] == {"value": 0, "limit": 0}
    assert c["device_launches"]["value"] >= 1
    cell = run.Cell(tiny_bench, CELL, _rig())
    listed = {m["name"] for m in cell.per_layer}
    assert set(line["metrics"]) == listed - DEVICE_ONLY
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # 68 texts over a prep cache of 8: within one deal no text is asked
    # twice, so none finds its memo.  Past one deal the clients drift
    # apart on a loaded machine (client c's i-th text is (8 i + c) mod 68:
    # two clients a lap apart ask the same text within a few requests),
    # and how many then share a score is the machine's to say, not the
    # program's: the mechanism still has to score most of them.
    if line["attempted"] <= 68:
        assert m["exec.topn_scored_share"] >= 90.0
    else:
        assert m["exec.topn_scored_share"] > 50.0
    # every answer selected from the entry's stacked arrays: no call a part
    assert m["exec.topn_select_stacked_share"] == 100.0
    assert m["device.window_new_programs"] == 0 and m["device.window_compile_ms"] == 0
    assert m["exec.topn_prep_ms"] > 0 and m["device.topn_dispatch_ms"] > 0


def test_an_altered_answer_under_the_cell_is_not_correct(in_a_test_process, tiny_bench):
    argv = [sys.executable, os.path.join(BENCH, "tests", "broken_server.py"),
            "answer_altered", "server"]
    rc, line = run.run_cell(tiny_bench, CELL, 2_900_000_035, 1.0, False, _rig(argv))
    assert rc == 0
    assert line["correct"] is False
    assert line["compared"]["wrong_answers"]["value"] > 0
    assert set(line["metrics"]) == {"answers_per_s", "setup_s"}


@pytest.mark.parametrize("seed", [3, 2_900_000_041])
def test_both_controls_are_refused_and_the_sound_reference_is_not(tiny_bench, seed):
    cell = run.Cell(tiny_bench, CELL, _rig())
    ref = cell.kind.Reference(cell.config, seed)
    for unit in ref.units():
        ref.make(unit)
    ref.seal()
    traffic = cell.kind.Traffic(cell.mix, cell.config, seed)
    assert control.judge(ref, traffic, 68, None)["correct"] is True
    for broken in cell.kind.CONTROLS:
        verdict = control.judge(ref, traffic, 68, broken)
        assert verdict["correct"] is False
        # every text whose src the tiny index holds comes out wrong
        assert verdict["compared"]["wrong_answers"]["value"] >= 12
