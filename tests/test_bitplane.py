"""Bit-plane op parity vs numpy (the kernel-level parity tier, replacing
the reference's asm-vs-Go popcount tests, roaring/assembly_test.go:20-43)."""

import numpy as np
import pytest

from pilosa_tpu.ops import bitplane as bp


def random_row(rng, density=0.01):
    n = int(bp.SLICE_WIDTH * density)
    offs = rng.choice(bp.SLICE_WIDTH, size=n, replace=False)
    return bp.np_columns_to_row(offs), np.sort(offs)


def np_popcount(words):
    return int(np.unpackbits(words.view(np.uint8)).sum())


def test_set_clear_contains(rng):
    plane = bp.empty_plane(4)
    assert bp.np_set_bit(plane, 5)
    assert not bp.np_set_bit(plane, 5)
    assert bp.np_contains(plane, 5)
    assert bp.np_set_bit(plane, bp.SLICE_WIDTH + 7)  # row 1
    assert plane[1, 0] == 1 << 7
    assert bp.np_clear_bit(plane, 5)
    assert not bp.np_clear_bit(plane, 5)
    assert not bp.np_contains(plane, 5)


def test_columns_roundtrip(rng):
    row, offs = random_row(rng)
    got = bp.np_row_to_columns(row)
    assert np.array_equal(got, offs.astype(np.uint64))


def test_count_ops_match_numpy(rng):
    a, _ = random_row(rng, 0.02)
    b, _ = random_row(rng, 0.02)
    assert int(bp.count(a)) == np_popcount(a)
    assert int(bp.count_and(a, b)) == np_popcount(a & b)
    assert int(bp.count_or(a, b)) == np_popcount(a | b)
    assert int(bp.count_xor(a, b)) == np_popcount(a ^ b)
    assert int(bp.count_andnot(a, b)) == np_popcount(a & ~b)


def test_materializing_ops(rng):
    a, _ = random_row(rng, 0.02)
    b, _ = random_row(rng, 0.02)
    assert np.array_equal(np.asarray(bp.and_(a, b)), a & b)
    assert np.array_equal(np.asarray(bp.or_(a, b)), a | b)
    assert np.array_equal(np.asarray(bp.xor(a, b)), a ^ b)
    assert np.array_equal(np.asarray(bp.andnot(a, b)), a & ~b)


@pytest.mark.parametrize(
    "start,end",
    [(0, 0), (0, 1), (31, 33), (0, bp.SLICE_WIDTH), (100, 100), (65, 64), (1000, 123456)],
)
def test_count_range(rng, start, end):
    a, offs = random_row(rng, 0.01)
    expect = int(((offs >= start) & (offs < end)).sum())
    assert int(bp.count_range(a, start, end)) == expect


def test_flip_range(rng):
    a, offs = random_row(rng, 0.001)
    start, end = 1000, 200000
    flipped = np.asarray(bp.flip_range(a, start, end))
    # bits inside [start,end) toggled, outside unchanged
    got = set(int(x) for x in bp.np_row_to_columns(flipped))
    expect = set(int(o) for o in offs)
    expect = (expect - set(range(start, end))) | (
        set(range(start, end)) - set(int(o) for o in offs)
    )
    assert got == expect


def test_row_counts_and_top_counts(rng):
    plane = bp.empty_plane(8)
    for r in range(8):
        n = (r + 1) * 100
        offs = rng.choice(bp.SLICE_WIDTH, size=n, replace=False)
        plane[r] = bp.np_columns_to_row(offs)
    counts = np.asarray(bp.row_counts(plane))
    for r in range(8):
        assert counts[r] == np_popcount(plane[r])
    src = plane[3]
    tc = np.asarray(bp.top_counts(plane, src))
    for r in range(8):
        assert tc[r] == np_popcount(plane[r] & src)


@pytest.mark.parametrize(
    "plane_rows, cand",
    [(16, 8), (16, 16), (8, 16)],
    ids=["gathers_its_candidates", "reads_the_plane_whole", "shorter_than_its_slots"],
)
def test_score_planes_parity(rng, plane_rows, cand):
    """The fused cross-fragment TopN scorer (gather + AND + popcount +
    rowsum straight from plane mirrors) matches numpy bit-for-bit, in
    both src modes; and where a plane is no taller than its slots, so
    does the TPU's kernel, which reads the plane whole and picks after
    (interpreted here)."""
    import jax.numpy as jnp

    n_frag = 3
    planes_np = [
        rng.integers(0, 2**32, size=(plane_rows, bp.WORDS_PER_SLICE), dtype=np.uint32)
        for _ in range(n_frag)
    ]
    slots = rng.integers(0, plane_rows, size=(n_frag, cand)).astype(np.int32)
    src_slots = rng.integers(0, plane_rows, size=n_frag).astype(np.int32)
    planes = tuple(jnp.asarray(p) for p in planes_np)

    want = np.zeros((n_frag, cand), np.int32)
    for f in range(n_frag):
        src = planes_np[f][src_slots[f]]
        for r in range(cand):
            want[f, r] = np.bitwise_count(
                planes_np[f][slots[f, r]] & src
            ).sum()

    def fetched(outs):
        # one launch per score_group_bucket members, the last one padded
        return np.concatenate([np.asarray(o) for o in outs])[:n_frag]

    got = fetched(bp.score_planes(planes, slots, src_slots=src_slots))
    np.testing.assert_array_equal(got, want)

    srcs = np.stack([planes_np[f][src_slots[f]] for f in range(n_frag)])
    got2 = fetched(bp.score_planes(planes, slots, srcs=srcs))
    np.testing.assert_array_equal(got2, want)

    assert bp.kernel_scores((plane_rows, bp.WORDS_PER_SLICE), cand) == (plane_rows <= cand)
    if plane_rows <= cand:
        got3 = bp._score_planes_kernel(planes, slots, src_slots, interpret=True)
        np.testing.assert_array_equal(np.asarray(got3), want)


def test_top_k_tie_break(rng):
    counts = np.array([5, 9, 9, 1, 9, 0], dtype=np.int32)
    topc, topidx = bp.top_k(counts, 3)
    assert list(np.asarray(topc)) == [9, 9, 9]
    assert list(np.asarray(topidx)) == [1, 2, 4]


def test_bulk_set(rng):
    plane = bp.empty_plane(4)
    rows = np.array([0, 0, 1, 3, 3])
    offs = np.array([0, 31, 32, 5, 5])
    bp.np_set_bulk(plane, rows, offs)
    assert bp.np_contains(plane, 0)
    assert bp.np_contains(plane, 31)
    assert bp.np_contains(plane, bp.SLICE_WIDTH + 32)
    assert bp.np_contains(plane, 3 * bp.SLICE_WIDTH + 5)
    assert np_popcount(plane) == 4


def test_pad_rows():
    assert bp.pad_rows(0) == bp.ROW_BLOCK
    assert bp.pad_rows(1) == bp.ROW_BLOCK
    assert bp.pad_rows(8) == 8
    assert bp.pad_rows(9) == 16


