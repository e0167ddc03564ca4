"""Plane-major multi-query fusion tests (exec/plan.py interpreter +
exec/coalesce.py program-key tier + executor wiring).

The acceptance bar: a mixed storm of DISTINCT Count/Range/TopN trees is
byte-identical across the fused, coalesce-only, and direct paths
(including BSI predicates at declared min/max boundaries); identical
queries within a fused batch share one lowered program and the emitter
dedups shared subtrees; a tree that exceeds the opcode-table bucket
falls back to the per-compile-key coalesce path rather than failing;
and a concurrent storm's launches stay well under its query count, with
the interpreter program-cache entries flat as mix diversity grows.
"""

import concurrent.futures
import math
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from pilosa_tpu import bsi
from pilosa_tpu.cluster.topology import new_cluster
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.exec import Executor, plan
from pilosa_tpu.exec.coalesce import CoalesceScheduler
from pilosa_tpu.ops.bitplane import SLICE_WIDTH, WORDS_PER_SLICE
from pilosa_tpu.pql.parser import parse_string

WAIT_US = 200_000


@pytest.fixture
def holder(tmp_path):
    h = Holder(str(tmp_path / "data"))
    h.open()
    yield h
    h.close()


def _canon(result):
    if hasattr(result, "bits"):
        return ("bits", tuple(result.bits()))
    if isinstance(result, list):
        return ("pairs", tuple((p.id, p.count) for p in result))
    return ("val", int(result))


# ---------------------------------------------------------------------------
# lowering + interpreter units
# ---------------------------------------------------------------------------


def test_emitter_value_numbering_dedups_commuted_subtrees():
    em = plan.FuseEmitter(4)
    a = em.and_(0, 1)
    b = em.and_(1, 0)  # commutative operand order normalizes
    assert a == b and em.dedup_hits == 1
    c = em.andnot(0, 1)
    d = em.andnot(1, 0)  # andnot is NOT commutative
    assert c != d
    assert em.maskw(2, 3) == em.maskw(2, 3)
    assert em.dedup_hits == 2


def test_emitter_rollback_restores_table():
    em = plan.FuseEmitter(2, max_ops=4)
    em.and_(0, 1)
    cp = em.checkpoint()
    em.or_(0, 1)
    em.xor(0, 1)
    em.rollback(cp)
    assert len(em.rows) == 1
    # memo entries past the checkpoint are gone: re-emitting allocates
    # fresh registers instead of referencing truncated ones.
    r = em.or_(0, 1)
    assert r == em.n_leaves + 1


def test_emitter_op_budget_raises():
    em = plan.FuseEmitter(2, max_ops=2)
    em.and_(0, 1)
    em.or_(0, 1)
    with pytest.raises(plan.FuseUnsupported):
        em.xor(0, 1)


def test_lower_expr_matches_eval_expr_np_random(rng):
    """Randomized trees (folds over leaves, nested) evaluate
    byte-identically between the interpreter and the numpy host
    reference."""
    words = 128
    exprs = [
        ("leaf", 0),
        ("Intersect", ("leaf", 0), ("leaf", 1)),
        ("Union", ("leaf", 0), ("Intersect", ("leaf", 1), ("leaf", 2))),
        ("Difference", ("leaf", 0), ("leaf", 1), ("leaf", 2)),
        ("Xor", ("Union", ("leaf", 0), ("leaf", 1)), ("leaf", 2)),
        (
            "Intersect",
            ("Union", ("leaf", 0), ("leaf", 1)),
            ("Difference", ("leaf", 2), ("leaf", 3)),
        ),
    ]
    for expr in exprs:
        n_leaves = max(_max_leaf(expr) + 1, 1)
        leaf_rows = [
            rng.integers(0, 2**32, size=words, dtype=np.uint32)
            for _ in range(n_leaves)
        ]
        want = plan.eval_expr_np(expr, leaf_rows, words)
        if want is None:
            want = np.zeros(words, dtype=np.uint32)
        em = plan.FuseEmitter(n_leaves)
        reg = plan.lower_expr(expr, 0, em)
        n_ops = max(len(em.rows), 1)
        prog = np.zeros((n_ops, 4), dtype=np.int32)
        if em.rows:
            prog[: len(em.rows)] = np.asarray(em.rows, dtype=np.int32)
        batch = np.stack(leaf_rows)[None]
        got = np.asarray(
            plan.interp_exec(
                "row", batch, prog, np.asarray([reg], dtype=np.int32)
            )
        )[0, 0]
        np.testing.assert_array_equal(got, want)


def _max_leaf(expr) -> int:
    if expr[0] == "leaf":
        return expr[1]
    return max((_max_leaf(e) for e in expr[1:] if isinstance(e, tuple)), default=0)


def test_lower_bsi_cmp_matches_ripple_all_ops(rng):
    """The lowered BSI ripple is byte-identical to the array ripple for
    every comparison op, positive and negative predicates included."""
    words = 64
    depth = 8
    exists = np.full(words, 0xFFFFFFFF, np.uint32)
    sign = rng.integers(0, 2**32, size=words, dtype=np.uint32)
    planes = rng.integers(0, 2**32, size=(depth, words), dtype=np.uint32)
    cases = [
        ("lt", 100), ("le", 0), ("eq", 37), ("ne", -3),
        ("ge", -120), ("gt", 255),
    ]
    for op, v in cases:
        pred = bsi.pred_row(v, depth)[: words]
        expr = ("bsiCmp", op) + tuple(("leaf", i) for i in range(depth + 3))
        leaf_rows = [exists, sign, *planes, pred]
        want = plan.eval_expr_np(expr, leaf_rows, words)
        em = plan.FuseEmitter(len(leaf_rows))
        reg = plan.lower_expr(expr, 0, em)
        prog = np.asarray(em.rows, dtype=np.int32)
        batch = np.stack(leaf_rows)[None]
        got = np.asarray(
            plan.interp_exec(
                "row", batch, prog, np.asarray([reg], dtype=np.int32)
            )
        )[0, 0]
        np.testing.assert_array_equal(got, want, err_msg=f"op={op} v={v}")


def test_lower_between_shares_subtrees(rng):
    """between = two ripples; the emitter's value numbering shares the
    sign-group rows between them (dedup fires)."""
    words = 32
    depth = 8
    exists = np.full(words, 0xFFFFFFFF, np.uint32)
    sign = rng.integers(0, 2**32, size=words, dtype=np.uint32)
    planes = rng.integers(0, 2**32, size=(depth, words), dtype=np.uint32)
    lo, hi = bsi.pred_row(-10, depth)[:words], bsi.pred_row(99, depth)[:words]
    expr = ("bsiCmp", "between") + tuple(
        ("leaf", i) for i in range(depth + 4)
    )
    leaf_rows = [exists, sign, *planes, lo, hi]
    want = plan.eval_expr_np(expr, leaf_rows, words)
    em = plan.FuseEmitter(len(leaf_rows))
    reg = plan.lower_expr(expr, 0, em)
    assert em.dedup_hits > 0  # pos/neg sign groups shared across ripples
    prog = np.asarray(em.rows, dtype=np.int32)
    batch = np.stack(leaf_rows)[None]
    got = np.asarray(
        plan.interp_exec("row", batch, prog, np.asarray([reg], np.int32))
    )[0, 0]
    np.testing.assert_array_equal(got, want)


def test_lower_bsi_aggregate_unsupported():
    expr = ("bsiSum", False) + tuple(("leaf", i) for i in range(10))
    with pytest.raises(plan.FuseUnsupported):
        plan.lower_expr(expr, 0, plan.FuseEmitter(10))


def test_canonicalize_call_commutes_and_preserves_difference():
    q1 = parse_string(
        "TopN(Intersect(Bitmap(rowID=1, frame=f), Bitmap(rowID=2, frame=f)),"
        " frame=t, n=2)"
    ).calls[0]
    q2 = parse_string(
        "TopN(Intersect(Bitmap(rowID=2, frame=f), Bitmap(rowID=1, frame=f)),"
        " frame=t, n=2)"
    ).calls[0]
    assert str(plan.canonicalize_call(q1)) == str(plan.canonicalize_call(q2))
    d1 = parse_string(
        "Difference(Bitmap(rowID=2, frame=f), Bitmap(rowID=1, frame=f))"
    ).calls[0]
    # Difference is not commutative: child order survives.
    assert str(plan.canonicalize_call(d1)) == str(d1)
    # Unchanged trees return the original object (cache keys stay
    # byte-identical for already-canonical queries).
    c = parse_string("Count(Bitmap(rowID=1, frame=f))").calls[0]
    assert plan.canonicalize_call(c) is c


# ---------------------------------------------------------------------------
# scheduler fusion
# ---------------------------------------------------------------------------


def test_fused_launch_distinct_exprs_one_launch(rng):
    co = CoalesceScheduler(max_wait_us=WAIT_US)
    try:
        words = 64
        batches = [
            jnp.asarray(
                rng.integers(0, 2**32, size=(4, 2, words), dtype=np.uint32)
            )
            for _ in range(3)
        ]
        exprs = [
            ("Intersect", ("leaf", 0), ("leaf", 1)),
            ("Union", ("leaf", 0), ("leaf", 1)),
            ("Xor", ("leaf", 0), ("leaf", 1)),
        ]
        futs = [
            co.submit(e, "count", b) for e, b in zip(exprs, batches)
        ]
        results = [f.result(timeout=30) for f in futs]
        fns = (np.bitwise_and, np.bitwise_or, np.bitwise_xor)
        for (res, info), b, fn in zip(results, batches, fns):
            h = np.asarray(b)
            want = np.bitwise_count(fn(h[:, 0], h[:, 1])).sum(axis=-1)
            np.testing.assert_array_equal(res, want)
            assert info["fused"] and info["programs"] == 3
        assert len({r[1]["launch"] for r in results}) == 1
        snap = co.snapshot()
        assert snap["fused_launches"] == 1
        assert snap["fused_queries"] == 3
    finally:
        co.close()


def test_fused_launch_identical_queries_share_program(rng):
    """N waiters of one (expr, batch) + M distinct queries: the
    identical ones share a single lowered program (identical leaf sets
    evaluated once) — programs counts DISTINCT trees, not waiters."""
    co = CoalesceScheduler(max_wait_us=WAIT_US)
    try:
        words = 32
        b1 = jnp.asarray(
            rng.integers(0, 2**32, size=(2, 2, words), dtype=np.uint32)
        )
        b2 = jnp.asarray(
            rng.integers(0, 2**32, size=(2, 2, words), dtype=np.uint32)
        )
        e1 = ("Intersect", ("leaf", 0), ("leaf", 1))
        e2 = ("Union", ("leaf", 0), ("leaf", 1))
        futs = [co.submit(e1, "count", b1) for _ in range(5)]
        futs.append(co.submit(e2, "count", b2))
        results = [f.result(timeout=30) for f in futs]
        info = results[0][1]
        assert info["fused"]
        assert info["batch_queries"] == 6
        assert info["programs"] == 2  # 5 identical waiters -> 1 program
        h1 = np.asarray(b1)
        want = np.bitwise_count(h1[:, 0] & h1[:, 1]).sum(axis=-1)
        for res, _ in results[:5]:
            np.testing.assert_array_equal(res, want)
    finally:
        co.close()


def test_union_leaf_sharing_collapses_columns(rng):
    """Two DISTINCT queries whose batches carry the same leaf identity
    key share ONE union register — the fused pass streams the shared
    plane row once (shared_leaves counts the collapse), and a common
    subtree over shared leaves dedups ACROSS the two queries."""
    co = CoalesceScheduler(max_wait_us=WAIT_US)
    try:
        words = 64
        rows = rng.integers(0, 2**32, size=(3, words), dtype=np.uint32)
        b1 = jnp.asarray(np.stack([rows[0], rows[1]])[None])  # [1, 2, w]
        b2 = jnp.asarray(np.stack([rows[0], rows[1], rows[2]])[None])
        k0, k1, k2 = ("r", 0), ("r", 1), ("r", 2)
        e1 = ("Intersect", ("leaf", 0), ("leaf", 1))
        e2 = ("Xor", ("Intersect", ("leaf", 0), ("leaf", 1)), ("leaf", 2))
        f1 = co.submit(e1, "count", b1, leaf_keys=(k0, k1))
        f2 = co.submit(e2, "count", b2, leaf_keys=(k0, k1, k2))
        (r1, i1), (r2, i2) = f1.result(timeout=30), f2.result(timeout=30)
        assert int(r1[0]) == int(np.bitwise_count(rows[0] & rows[1]).sum())
        assert int(r2[0]) == int(
            np.bitwise_count((rows[0] & rows[1]) ^ rows[2]).sum()
        )
        assert i1["fused"] and i1["programs"] == 2
        # 5 raw columns collapse to the 3-leaf union.
        assert i1["leaf_rows"] == 3 and i1["shared_leaves"] == 2
        # q2's Intersect(l0, l1) subtree reuses q1's lowered op.
        assert i1["dedup_hits"] >= 1
        assert co.snapshot()["fuse_shared_leaves"] == 2
    finally:
        co.close()


def test_fuse_row_reduce_scatters_rows(rng):
    co = CoalesceScheduler(max_wait_us=WAIT_US)
    try:
        words = 32
        b1 = jnp.asarray(
            rng.integers(0, 2**32, size=(2, 2, words), dtype=np.uint32)
        )
        b2 = jnp.asarray(
            rng.integers(0, 2**32, size=(2, 3, words), dtype=np.uint32)
        )
        e1 = ("Intersect", ("leaf", 0), ("leaf", 1))
        e2 = ("Union", ("leaf", 0), ("leaf", 1), ("leaf", 2))
        f1 = co.submit(e1, "row", b1)
        f2 = co.submit(e2, "row", b2)
        (r1, i1), (r2, i2) = f1.result(timeout=30), f2.result(timeout=30)
        h1, h2 = np.asarray(b1), np.asarray(b2)
        np.testing.assert_array_equal(r1, h1[:, 0] & h1[:, 1])
        np.testing.assert_array_equal(r2, h2[:, 0] | h2[:, 1] | h2[:, 2])
        assert i1["fused"] and i1["leaf_rows"] == 5 and i1["pad_leaves"] == 3
    finally:
        co.close()


def test_fuse_oversized_tree_falls_back_to_coalesce(rng):
    """A tree whose lowering exceeds the opcode-table bucket rides the
    ordinary per-compile-key concat launch — correct results, fused
    counters untouched for it, fallback counter incremented."""
    co = CoalesceScheduler(max_wait_us=WAIT_US)
    try:
        words = 16
        n_leaves = plan.FUSE_MAX_OPS + 2  # fold ops = n_leaves - 1 > budget
        big = jnp.asarray(
            rng.integers(
                0, 2**32, size=(1, n_leaves, words), dtype=np.uint32
            )
        )
        small = jnp.asarray(
            rng.integers(0, 2**32, size=(1, 2, words), dtype=np.uint32)
        )
        big_expr = ("Union",) + tuple(("leaf", i) for i in range(n_leaves))
        small_expr = ("Intersect", ("leaf", 0), ("leaf", 1))
        f_big = co.submit(big_expr, "count", big)
        f_small = co.submit(small_expr, "count", small)
        (rb, ib) = f_big.result(timeout=60)
        (rs, _is) = f_small.result(timeout=60)
        hb, hs = np.asarray(big), np.asarray(small)
        want_b = np.bitwise_count(
            np.bitwise_or.reduce(hb[0], axis=0)
        ).sum()
        np.testing.assert_array_equal(rb, [want_b])
        np.testing.assert_array_equal(
            rs, np.bitwise_count(hs[:, 0] & hs[:, 1]).sum(axis=-1)
        )
        assert not ib.get("fused")
        assert co.snapshot()["fuse_fallbacks"] >= 1
    finally:
        co.close()


def test_fuse_scratch_budget_splits_then_falls_back(rng, monkeypatch):
    """MAX_FUSE_BYTES bounds the interpreter's scratch: FUSE_SCRATCH_FACTOR
    register files (batch rows x (leaf bucket + op bucket) slice-rows),
    which is what the chip's compiler allots it: a program set past it
    splits into further fused launches, and a pair that still does not
    fit rides the concat path — same answers either way."""
    from pilosa_tpu.exec import coalesce as coalesce_mod

    words, rows = 16, 4
    exprs = [
        ("Intersect", ("leaf", 0), ("leaf", 1)),
        ("Union", ("leaf", 0), ("leaf", 1)),
        ("Difference", ("leaf", 0), ("leaf", 1)),
        ("Xor", ("leaf", 0), ("leaf", 1)),
    ]
    np_ops = [np.bitwise_and, np.bitwise_or, lambda a, b: a & ~b, np.bitwise_xor]

    def storm(budget):
        monkeypatch.setattr(coalesce_mod, "MAX_FUSE_BYTES", budget)
        co = CoalesceScheduler(max_wait_us=WAIT_US)
        try:
            batches = [
                jnp.asarray(rng.integers(
                    0, 2**32, size=(rows, 2, words), dtype=np.uint32
                ))
                for _ in exprs
            ]
            futs = [co.submit(e, "count", b) for e, b in zip(exprs, batches)]
            for fut, b, op in zip(futs, batches, np_ops):
                got, _info = fut.result(timeout=60)
                h = np.asarray(b)
                np.testing.assert_array_equal(
                    got, np.bitwise_count(op(h[:, 0], h[:, 1])).sum(axis=-1)
                )
            return co.snapshot()
        finally:
            co.close()

    # Four programs over 8 distinct leaves: one launch holds
    # rows x (8 + 8) slice-rows of registers; two programs over 4
    # leaves hold rows x (4 + 8).
    per_device = rows // len(jnp.zeros(1).devices())
    factor = coalesce_mod.FUSE_SCRATCH_FACTOR
    whole = math.ceil(factor * per_device * (8 + 8) * words * 4)
    halved = math.ceil(factor * per_device * (4 + 8) * words * 4)
    snap = storm(whole)
    assert snap["fused_launches"] == 1 and snap["fused_queries"] == 4
    snap = storm(halved)
    assert snap["fused_launches"] == 2 and snap["fused_queries"] == 4
    snap = storm(halved - 1)
    assert snap["fused_launches"] == 0 and snap["fuse_fallbacks"] == 4


def test_fuse_disabled_keeps_concat_semantics(rng):
    co = CoalesceScheduler(max_wait_us=WAIT_US, fuse=False)
    try:
        words = 16
        b1 = jnp.asarray(
            rng.integers(0, 2**32, size=(1, 2, words), dtype=np.uint32)
        )
        b2 = jnp.asarray(
            rng.integers(0, 2**32, size=(1, 2, words), dtype=np.uint32)
        )
        f1 = co.submit(("Intersect", ("leaf", 0), ("leaf", 1)), "count", b1)
        f2 = co.submit(("Union", ("leaf", 0), ("leaf", 1)), "count", b2)
        (r1, i1) = f1.result(timeout=30)
        (r2, _) = f2.result(timeout=30)
        h1, h2 = np.asarray(b1), np.asarray(b2)
        assert int(r1[0]) == int(np.bitwise_count(h1[:, 0] & h1[:, 1]).sum())
        assert int(r2[0]) == int(np.bitwise_count(h2[:, 0] | h2[:, 1]).sum())
        assert not i1.get("fused")
        assert co.snapshot()["fused_launches"] == 0
    finally:
        co.close()


def test_shared_fetch_batches_round_trips(rng):
    co = CoalesceScheduler(max_wait_us=WAIT_US)
    try:
        arrs = [
            jnp.asarray(
                rng.integers(0, 2**32, size=(4, 8), dtype=np.uint32)
            )
            for _ in range(4)
        ]
        futs = [co.submit_fetch([a]) for a in arrs]
        results = [f.result(timeout=30) for f in futs]
        for (hosts, info), a in zip(results, arrs):
            np.testing.assert_array_equal(np.asarray(hosts[0]), np.asarray(a))
        # All four items drained in one device_get round trip.
        assert len({r[1]["fetch_launch"] for r in results}) == 1
        assert co.snapshot()["fetch_launches"] == 1
    finally:
        co.close()


# ---------------------------------------------------------------------------
# executor integration: mixed storms byte-identical across all paths
# ---------------------------------------------------------------------------

BSI_MIN, BSI_MAX = -128, 127


def _seed_mixed(holder, rng):
    idx = holder.create_index("i")
    f = idx.create_frame("f", cache_size=64)
    bits = [
        (1, 0), (1, 3), (1, SLICE_WIDTH + 1), (1, 2 * SLICE_WIDTH + 5),
        (2, 3), (2, SLICE_WIDTH + 1), (2, SLICE_WIDTH + 9),
        (3, 7), (3, 2 * SLICE_WIDTH + 5), (4, 11), (4, SLICE_WIDTH + 2),
    ]
    for row, col in bits:
        f.set_bit("standard", row, col)
    f.set_options(range_enabled=True)
    f.create_field("v", BSI_MIN, BSI_MAX)
    vals = {}
    for col in range(0, 3 * SLICE_WIDTH, SLICE_WIDTH // 7):
        v = int(rng.integers(BSI_MIN, BSI_MAX + 1))
        vals[col] = v
        f.import_value("v", [col], [v])
    ft = idx.create_frame("t", cache_size=64)
    for row in range(6):
        for col in range(0, 2 * SLICE_WIDTH, SLICE_WIDTH // (5 + row)):
            ft.set_bit("standard", row, col)
    return vals


# Mixed distinct trees: point counts, rows, BSI ranges INCLUDING the
# declared min/max boundaries, and TopN(src).
MIXED = [
    "Count(Intersect(Bitmap(rowID=1, frame=f), Bitmap(rowID=2, frame=f)))",
    "Count(Union(Bitmap(rowID=1, frame=f), Bitmap(rowID=3, frame=f)))",
    "Count(Difference(Bitmap(rowID=2, frame=f), Bitmap(rowID=4, frame=f)))",
    "Bitmap(rowID=1, frame=f)",
    "Union(Bitmap(rowID=2, frame=f), Bitmap(rowID=3, frame=f))",
    f"Count(Range(frame=f, v > {BSI_MIN}))",
    f"Count(Range(frame=f, v >= {BSI_MIN}))",
    f"Count(Range(frame=f, v < {BSI_MAX}))",
    f"Count(Range(frame=f, v <= {BSI_MAX}))",
    "Count(Range(frame=f, v == 0))",
    f"Count(Range(frame=f, v >< [{BSI_MIN}, {BSI_MAX}]))",
    "Count(Range(frame=f, v > 17))",
    "Count(Intersect(Bitmap(rowID=1, frame=f), Range(frame=f, v < -5)))",
    "TopN(Bitmap(rowID=0, frame=t), frame=t, n=3)",
    "TopN(frame=t, n=2)",
]


def test_mixed_storm_byte_identical_fused_coalesce_direct(holder, rng):
    _seed_mixed(holder, rng)
    c = new_cluster(1)
    host = c.nodes[0].host
    plain = Executor(holder, host=host, cluster=c)
    try:
        expected = [
            _canon(plain.execute("i", parse_string(q))[0]) for q in MIXED
        ]
    finally:
        plain.close()

    for fuse_on in (False, True):
        co = CoalesceScheduler(max_wait_us=WAIT_US, fuse=fuse_on)
        ex = Executor(holder, host=host, cluster=c, coalescer=co)
        try:
            got = [
                _canon(ex.execute("i", parse_string(q))[0]) for q in MIXED
            ]
            assert got == expected, f"serial fuse={fuse_on}"

            def run_mix(t):
                # Stagger each thread's starting point so DISTINCT
                # trees co-queue (lockstep threads would only ever
                # exercise identity dedup).
                order = list(range(t, len(MIXED))) + list(range(t))
                got = [None] * len(MIXED)
                for i in order:
                    got[i] = _canon(ex.execute("i", parse_string(MIXED[i]))[0])
                return got

            with concurrent.futures.ThreadPoolExecutor(8) as pool:
                for got in pool.map(run_mix, range(8)):
                    assert got == expected, f"concurrent fuse={fuse_on}"
            if fuse_on:
                snap = co.snapshot()
                assert snap["fused_launches"] >= 1
                assert snap["fused_queries"] > snap["fused_launches"]
        finally:
            ex.close()
            co.close()


def test_concurrent_distinct_storm_launches_far_below_queries(holder, rng):
    """The headline invariant: a storm of DISTINCT queries rides far
    fewer launches than queries via the fusion tier (the old coalescer
    could only do this for identical queries)."""
    _seed_mixed(holder, rng)
    c = new_cluster(1)
    co = CoalesceScheduler(max_wait_us=WAIT_US)
    ex = Executor(holder, host=c.nodes[0].host, cluster=c, coalescer=co)
    try:
        queries = [
            parse_string(q)
            for q in MIXED
            if q.startswith("Count(") or q.startswith("Bitmap")
        ]
        # Warm every distinct batch cache entry serially.
        want = [_canon(ex.execute("i", q)[0]) for q in queries]
        before = co.snapshot()
        n = 48
        barrier = threading.Barrier(12)

        def one(i):
            barrier.wait(timeout=30)
            q = queries[i % len(queries)]
            assert _canon(ex.execute("i", q)[0]) == want[i % len(queries)]

        with concurrent.futures.ThreadPoolExecutor(12) as pool:
            list(pool.map(one, range(n)))
        snap = co.snapshot()
        launches = snap["launches"] - before["launches"]
        qn = snap["queries"] - before["queries"]
        assert qn == n
        assert launches < qn, (launches, qn)
        assert snap["fused_queries"] - before["fused_queries"] > 0
    finally:
        ex.close()
        co.close()


def test_interp_program_cache_flat_under_diversity(holder, rng):
    """exec.programCache.entries[cache:interp] is O(1) in mix
    diversity: doubling the distinct-predicate mix adds NO interpreter
    entries (opcode tables are data; geometry is the only jit key)."""
    _seed_mixed(holder, rng)
    c = new_cluster(1)
    co = CoalesceScheduler(max_wait_us=WAIT_US)
    ex = Executor(holder, host=c.nodes[0].host, cluster=c, coalescer=co)
    try:
        def storm(preds):
            queries = [
                parse_string(f"Count(Range(frame=f, v > {p}))") for p in preds
            ]
            for q in queries:
                ex.execute("i", q)
            barrier = threading.Barrier(8)

            def one(i):
                barrier.wait(timeout=30)
                ex.execute("i", queries[i % len(queries)])

            with concurrent.futures.ThreadPoolExecutor(8) as pool:
                list(pool.map(one, range(16)))

        storm((1, 2, 3, 4))
        entries = plan.program_cache_stats()["interp"]
        assert entries >= 1
        bounds = plan.program_cache_bounds()
        assert entries <= bounds["interp"]
        # Same tree GEOMETRY, brand-new predicates: zero new compiles.
        storm((11, 22, 33, 44, 55, 66, 77, 88))
        assert plan.program_cache_stats()["interp"] == entries
        assert plan.program_cache_stats()["interp"] <= (
            plan.program_cache_bounds()["interp"]
        )
    finally:
        ex.close()
        co.close()


def test_topn_canonical_key_shares_single_flight(holder, rng):
    """PR-10 single-flight keyed on the exact query string; the
    canonical compile key shares one dispatch across semantically
    identical TopN(src) queries whose src trees merely commute — and
    the results stay byte-identical."""
    _seed_mixed(holder, rng)
    c = new_cluster(1)
    ex = Executor(holder, host=c.nodes[0].host, cluster=c)
    try:
        q1 = parse_string(
            "TopN(Union(Bitmap(rowID=0, frame=t), Bitmap(rowID=1, frame=t)),"
            " frame=t, n=3)"
        )
        q2 = parse_string(
            "TopN(Union(Bitmap(rowID=1, frame=t), Bitmap(rowID=0, frame=t)),"
            " frame=t, n=3)"
        )
        (r1,) = ex.execute("i", q1)
        # Byte-identity across orderings.
        (r2,) = ex.execute("i", q2)
        assert _canon(r1) == _canon(r2)
        # The prep cache holds ONE entry for both orderings (the
        # canonical key), so the second ordering validated against the
        # first's entry instead of building its own.
        keys = list(ex._topn_cache.keys())
        assert len([k for k in keys if "Union" in k[1]]) == 1
    finally:
        ex.close()


def test_topn_commuted_storm_one_dispatch(holder, rng):
    """Concurrent commuted-ordering TopN storm: every query shares the
    leader's fetched scores (exec.topn.scoreShared fires; one entry)."""
    _seed_mixed(holder, rng)

    class CountingStats:
        def __init__(self):
            self.counts = {}

        def count(self, name, value=1, rate=1.0):
            self.counts[name] = self.counts.get(name, 0) + value

        def count_with_custom_tags(self, name, value, tags):
            self.count(name, value)

        def gauge(self, *a, **k):
            pass

        def histogram(self, *a, **k):
            pass

        def timing(self, *a, **k):
            pass

        def tags(self):
            return []

    holder.stats = CountingStats()
    c = new_cluster(1)
    ex = Executor(holder, host=c.nodes[0].host, cluster=c)
    try:
        texts = [
            "TopN(Union(Bitmap(rowID=2, frame=t), Bitmap(rowID=3, frame=t)),"
            " frame=t, n=3)",
            "TopN(Union(Bitmap(rowID=3, frame=t), Bitmap(rowID=2, frame=t)),"
            " frame=t, n=3)",
        ]
        queries = [parse_string(t) for t in texts]
        (want,) = ex.execute("i", queries[0])
        barrier = threading.Barrier(8)

        def one(i):
            barrier.wait(timeout=30)
            (got,) = ex.execute("i", queries[i % 2])
            assert _canon(got) == _canon(want)

        with concurrent.futures.ThreadPoolExecutor(8) as pool:
            list(pool.map(one, range(16)))
        # Both orderings rode the one validated entry: score sharing
        # fired (without canonicalization the second ordering would
        # have built its own entry and never shared).
        assert holder.stats.counts.get("exec.topn.scoreShared", 0) > 0
        union_keys = [k for k in ex._topn_cache if "Union" in k[1]]
        assert len(union_keys) == 1
    finally:
        ex.close()
