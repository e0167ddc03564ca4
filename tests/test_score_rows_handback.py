"""``bp.score_rows`` hands back the rows a text keeps in steps
(``bp.ROW_STEP`` of them a trip of a device loop whose trip count comes
from how many there are): the first ``ROW_HITS`` kept rows in slot
order with their shared bits, and the whole vector for a text that
keeps more.  Held here at the edges of a step and of a launch, against
``np.flatnonzero``, with the two sizes patched small so that a test's
plane stands for the cell's 2^21 rows."""

import jax.numpy as jnp
import numpy as np
import pytest

from pilosa_tpu.ops import bitplane as bp

STEP, HITS = 4, 32
WORDS = 4
KEPT = [0, 1, STEP - 1, STEP, STEP + 1, 3 * STEP + 5, HITS, HITS + 1]


@pytest.fixture(scope="module", autouse=True)
def shipped():
    """A step of 4 and a launch of 32, for this file's programs alone;
    yields the sizes the module ships with."""
    was = {"ROW_STEP": bp.ROW_STEP, "ROW_HITS": bp.ROW_HITS}
    mp = pytest.MonkeyPatch()
    mp.setattr(bp, "ROW_STEP", STEP)
    mp.setattr(bp, "ROW_HITS", HITS)
    bp._score_rows_xla.clear_cache()
    bp._SCORE_SEEN.clear()
    yield was
    mp.undo()
    bp._score_rows_xla.clear_cache()
    bp._SCORE_SEEN.clear()


def kept_rows(rows: int, hits: int, where: str) -> np.ndarray:
    """``hits`` slots of a ``rows``-row plane: packed from slot 0 (one
    block while they fit it), one a block of ``lanes`` rows (then
    round again), or packed up to the last slot."""
    lanes = min(128, rows)
    if where == "one_block":
        return np.arange(hits)
    if where == "last_block":
        return np.arange(rows - hits, rows)
    blocks = rows // lanes
    i = np.arange(hits)
    return np.sort((i % blocks) * lanes + (lanes - 1 - i // blocks))


@pytest.mark.parametrize("where", ["one_block", "a_block_each", "last_block"])
@pytest.mark.parametrize("rows", [8, 128, 4096])
@pytest.mark.parametrize("hits", KEPT)
def test_the_kept_rows_come_back_in_slot_order_with_their_shared_bits(hits, rows, where):
    hits = min(hits, rows)
    rng = np.random.default_rng(hits * 131 + rows)
    src_slot = rows - 1
    # every row shares 1..WORDS*32 bits with the all-ones src; a row is
    # kept where its cached count is not 0 (threshold= alone, at 1)
    plane = rng.integers(1, 2**32, size=(rows, WORDS), dtype=np.uint32)
    plane[src_slot] = 0xFFFFFFFF
    keep = kept_rows(rows, hits, where)
    cnts = np.zeros(rows, dtype=np.int32)
    cnts[keep] = np.bitwise_count(plane[keep]).sum(axis=1)
    got_hits, slots, shared, every = (
        np.asarray(a)
        for a in bp.score_rows(
            jnp.asarray(plane), jnp.asarray(cnts), src_slot, WORDS * 32, 0, 1
        )
    )
    c = np.bitwise_count(plane & plane[src_slot]).sum(axis=1)
    assert got_hits == len(keep)
    k = min(HITS, rows)
    assert slots.shape == shared.shape == (k,) and every.shape == (rows,)
    first = np.flatnonzero(cnts)[:k]
    assert np.array_equal(first, keep[:k])
    assert np.array_equal(slots[: len(first)], first)
    assert np.array_equal(shared[: len(first)], c[first])
    # the vector a caller fetches when more are kept than a launch compacts
    assert np.array_equal(np.flatnonzero(every), keep)
    assert np.array_equal(every[keep], c[keep])


def test_the_step_is_the_smaller_of_its_size_and_what_a_launch_hands_back(shipped):
    assert bp.row_step(HITS) == STEP and bp.row_step(2) == 2
    assert shipped == {"ROW_STEP": 1 << 10, "ROW_HITS": 1 << 14}
