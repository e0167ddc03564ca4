"""Compiled-program cardinality under schema churn (ROADMAP 2a).

The serving contract: compiled-program count is O(1) in schema shape.
Every jit compile key is canonicalized — plane rows, candidate slots,
fragment-group sizes, and batch slice axes all bucket to powers of two
— so a churny schema (many frames, each with a different row count)
reuses a handful of compiled programs instead of minting one per
fragment shape at ~326 ms of XLA compile each.

The regression tests below create >= 32 DISTINCT fragment-set /
plane-set shapes, run the standard query mix over every one on both
the direct and the coalesced executor paths, and assert via the
``exec.programCache.*`` gauges (plan.program_cache_stats) that each
jit family stays <= 4 compiled programs — with results byte-identical
to an unpadded host (numpy) evaluation.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest

from pilosa_tpu.core.holder import Holder
from pilosa_tpu.exec import plan
from pilosa_tpu.exec.coalesce import CoalesceScheduler
from pilosa_tpu.exec.executor import Executor
from pilosa_tpu.ops import bitplane as bp
from pilosa_tpu.pql.parser import parse_string

N_FRAMES = 32

BOUNDED_FAMILIES = (
    "plan.batched",
    "plan.totalCount",
    "bitplane.scorePlanes",
    "bitplane.topCounts",
)


def _frame_name(k: int) -> str:
    return f"f{k:02d}"


@pytest.fixture
def churny(tmp_path, rng):
    """One index, N_FRAMES frames; frame k holds a single slice-0
    fragment with k+1 rows — 32 distinct raw fragment shapes (and,
    after pow2 padding, exactly the {8, 16, 32} plane classes)."""
    holder = Holder(str(tmp_path))
    holder.open()
    idx = holder.create_index("i")
    bits: dict[str, dict[int, list[int]]] = {}
    for k in range(N_FRAMES):
        f = idx.create_frame(_frame_name(k), cache_size=64)
        view = f.create_view_if_not_exists("standard")
        frag = view.create_fragment_if_not_exists(0)
        rows = k + 1
        per_row: dict[int, list[int]] = {}
        for r in range(rows):
            cols = sorted(
                int(c)
                for c in np.unique(
                    rng.integers(0, bp.SLICE_WIDTH, size=r + 3)
                )
            )
            for c in cols:
                frag.set_bit(r, c)
            per_row[r] = cols
        bits[_frame_name(k)] = per_row
    yield holder, bits
    holder.close()


def _expected_count_and(per_row, r1: int, r2: int) -> int:
    return len(set(per_row[r1]) & set(per_row[r2]))


def _expected_topn(per_row, src_row: int, n: int):
    """Unpadded host reference: |row AND src| per row, (-count, id)."""
    src = set(per_row[src_row])
    scored = [
        (r, len(set(cols) & src)) for r, cols in per_row.items()
    ]
    scored = [(r, c) for r, c in scored if c > 0]
    scored.sort(key=lambda p: (-p[1], p[0]))
    return scored[:n] if n else scored


def _run_mix(ex, bits):
    """The standard mix over every churny frame: a 2-leaf
    Intersect+Count and a same-frame TopN(src).  Returns
    [(got_count, want_count, got_pairs, want_pairs)] per frame."""
    out = []
    for name, per_row in bits.items():
        rows = len(per_row)
        r2 = rows - 1
        q = parse_string(
            f"Count(Intersect(Bitmap(rowID=0, frame={name}),"
            f" Bitmap(rowID={r2}, frame={name})))"
        )
        (got_count,) = ex.execute("i", q)
        tq = parse_string(
            f"TopN(Bitmap(rowID=0, frame={name}), frame={name}, n={rows})"
        )
        (got_pairs,) = ex.execute("i", tq)
        out.append(
            (
                int(got_count),
                _expected_count_and(per_row, 0, r2),
                [(p.id, p.count) for p in got_pairs],
                _expected_topn(per_row, 0, rows),
            )
        )
    return out


def _assert_mix(results):
    for got_count, want_count, got_pairs, want_pairs in results:
        assert got_count == want_count
        assert got_pairs == want_pairs


def _assert_bounded(limit: int = 4):
    stats = plan.program_cache_stats()
    bounds = plan.program_cache_bounds()
    for fam in BOUNDED_FAMILIES:
        assert stats[fam] <= limit, (fam, stats)
        assert stats[fam] <= bounds[fam], (fam, stats, bounds)


class TestChurnySchemaCardinality:
    def test_direct_path(self, churny):
        holder, bits = churny
        plan.clear_program_caches()
        ex = Executor(holder)
        try:
            _assert_mix(_run_mix(ex, bits))
        finally:
            ex.close()
        # >= 32 distinct fragment shapes -> <= 4 programs per family.
        _assert_bounded()
        stats = plan.program_cache_stats()
        # the scorer DID run: since PR 36 a one-slice TopN(src) walks its
        # fragment's rows (bitplane.scoreRows), bounded as the rest
        assert stats["bitplane.scorePlanes"] + stats["bitplane.scoreRows"] >= 1
        assert stats["bitplane.scoreRows"] <= min(
            4, plan.program_cache_bounds()["bitplane.scoreRows"])

    def test_coalesced_path(self, churny):
        holder, bits = churny
        plan.clear_program_caches()
        co = CoalesceScheduler()
        ex = Executor(holder, coalescer=co)
        try:
            _assert_mix(_run_mix(ex, bits))
        finally:
            ex.close()
            co.close()
        _assert_bounded()

    def test_direct_and_coalesced_agree(self, churny):
        """Byte-identical results whichever path compiled the programs."""
        holder, bits = churny
        plan.clear_program_caches()
        ex1 = Executor(holder)
        co = CoalesceScheduler()
        ex2 = Executor(holder, coalescer=co)
        try:
            direct = _run_mix(ex1, bits)
            coalesced = _run_mix(ex2, bits)
        finally:
            ex1.close()
            ex2.close()
            co.close()
        for d, c in zip(direct, coalesced):
            assert d[0] == c[0] and d[2] == c[2]
        _assert_bounded()


class TestBucketHelpers:
    def test_pad_rows_pow2_classes(self):
        # 1..32 raw row counts land in exactly 3 shape classes.
        classes = {bp.pad_rows(r) for r in range(1, 33)}
        assert classes == {8, 16, 32}
        assert bp.pad_rows(0) == bp.ROW_BLOCK
        assert bp.pad_rows(33) == 64

    def test_bucket_classes(self):
        assert bp.bucket_classes(8, 8) == 1
        assert bp.bucket_classes(32, 8) == 3
        assert bp.bucket_classes(256, 8) == 6
        assert bp.bucket_classes(1) == 1
        assert bp.bucket_classes(4) == 3  # {1, 2, 4}

    def test_slice_bucket(self):
        assert [plan.slice_bucket(n) for n in (1, 2, 3, 5, 9)] == [
            1,
            2,
            4,
            8,
            16,
        ]

    def test_wider_churn_stays_under_bucket_count(self, tmp_path, rng):
        """Row counts spanning 8..256 (32 distinct multiples of 8 — the
        shapes that each minted a program under the old multiple-of-8
        padding) stay within the pow2 bucket-class bound."""
        plan.clear_program_caches()
        holder = Holder(str(tmp_path))
        holder.open()
        idx = holder.create_index("i")
        ex = Executor(holder)
        try:
            for k in range(1, 33):
                name = f"w{k:02d}"
                f = idx.create_frame(name, cache_size=512)
                view = f.create_view_if_not_exists("standard")
                frag = view.create_fragment_if_not_exists(0)
                rows = 8 * k  # 8, 16, ..., 256
                for r in range(rows):
                    frag.set_bit(r, (r * 37) % bp.SLICE_WIDTH)
                    frag.set_bit(r, (r * 91 + 7) % bp.SLICE_WIDTH)
                tq = parse_string(
                    f"TopN(Bitmap(rowID=0, frame={name}), frame={name}, n=4)"
                )
                ex.execute("i", tq)
            stats = plan.program_cache_stats()
            bounds = plan.program_cache_bounds()
            # The satellite bar: each family <= its bucket count.  The
            # slot/row grids over [8, 256] have 6 pow2 classes; the old
            # multiple-of-8 padding produced up to 32 per family here.
            assert stats["bitplane.scorePlanes"] <= bounds[
                "bitplane.scorePlanes"
            ]
            assert stats["bitplane.scorePlanes"] <= 2 * bp.bucket_classes(
                256, bp.ROW_BLOCK
            ) ** 2
            assert stats["bitplane.topCounts"] <= bounds["bitplane.topCounts"]
        finally:
            ex.close()
            holder.close()


def test_program_cache_bounds_invariant_after_prewarm():
    """entries <= bound must hold after the standard prewarm too — the
    invariant the /metrics bound gauges advertise."""
    from pilosa_tpu.exec import warmup

    plan.clear_program_caches()
    warmup.prewarm(buckets=(1, 2), exprs=warmup._STANDARD_EXPRS[:2])
    stats = plan.program_cache_stats()
    bounds = plan.program_cache_bounds()
    for fam, bound in bounds.items():
        assert stats[fam] <= bound, (fam, stats, bounds)
    assert stats["total"] > 0


def _top_counts_on(dev):
    plane = jax.device_put(np.zeros((bp.ROW_BLOCK, bp.WORDS_PER_SLICE), np.uint32), dev)
    bp.top_counts(plane, plane[0])


def _expand_on(dev):
    payload = np.full(bp.PAYLOAD_BUCKET_FLOOR, 7, np.uint32)
    bp.expand_payload(bp.FMT_SPARSE, jax.device_put(payload, dev))


def _score_on(dev):
    plane = jax.device_put(np.zeros((bp.ROW_BLOCK, bp.WORDS_PER_SLICE), np.uint32), dev)
    bp.score_planes(
        [plane], np.zeros((1, bp.ROW_BLOCK), np.int32),
        src_slots=np.zeros(1, np.int32),
    )


def _aggregate_on(dev):
    plane = jax.device_put(np.zeros((bp.ROW_BLOCK, bp.WORDS_PER_SLICE), np.uint32), dev)
    expr = ("bsiSum", False) + tuple(("leaf", j) for j in range(10))
    cols = (("row", 0, 0), ("zero",), ("row", 0, 1)) + (("zero",),) * 7
    bp.aggregate_planes(
        plan._eval_expr, expr, cols, ("whole",), [plane],
        np.zeros((1, 2), np.int32), np.zeros((0, bp.PRED_WORDS), np.uint32),
    )


@pytest.mark.parametrize(
    "family, launch",
    [
        ("bitplane.topCounts", _top_counts_on),
        ("bitplane.expand", _expand_on),
        ("bitplane.scorePlanes", _score_on),
        ("bitplane.aggregatePlanes", _aggregate_on),
    ],
)
def test_a_program_over_per_device_planes_is_bounded_on_every_device(family, launch):
    """A plane lives on its slice's home device and the jit compiles
    one executable a device: the bound counts them, whatever this
    process ran before."""
    devices = bp.participating_devices()
    assert len(devices) > 1
    before = plan.program_cache_stats()[family]
    for dev in devices:
        launch(dev)
    stats, bounds = plan.program_cache_stats(), plan.program_cache_bounds()
    assert stats[family] - before <= len(devices)
    assert len(devices) <= stats[family] <= bounds[family], (stats, bounds)
