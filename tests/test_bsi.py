"""BSI integer fields: schema, import, ripple correctness, program
sharing, and the cluster path.

The heart is the randomized property check: Range/Sum/Min/Max results
must be byte-identical to a per-column NumPy reference on data that
includes negatives and the declared min/max boundaries — on the direct
device path, the coalesced path, and across a real 2-node cluster.
"""

import json
import threading
import time

import numpy as np
import pytest

from pilosa_tpu import bsi
from pilosa_tpu.core.frame import FrameError
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.exec import plan
from pilosa_tpu.exec.coalesce import CoalesceScheduler
from pilosa_tpu.exec.executor import Executor, ExecutorError
from pilosa_tpu.ops.bitplane import SLICE_WIDTH
from pilosa_tpu.pql import parse_string

OPS = {
    "<": lambda v, p: v < p,
    "<=": lambda v, p: v <= p,
    "==": lambda v, p: v == p,
    "!=": lambda v, p: v != p,
    ">=": lambda v, p: v >= p,
    ">": lambda v, p: v > p,
}


# ---------------------------------------------------------------------------
# units
# ---------------------------------------------------------------------------


def test_bit_depth_for():
    assert bsi.bit_depth_for(0, 0) == 1
    assert bsi.bit_depth_for(0, 1) == 1
    assert bsi.bit_depth_for(0, 255) == 8
    assert bsi.bit_depth_for(0, 256) == 9
    assert bsi.bit_depth_for(-1000, 10) == 10  # |min| dominates
    assert bsi.bit_depth_for(-3, 1000) == 10


def test_pad_depth_buckets():
    assert bsi.pad_depth(1) == 8
    assert bsi.pad_depth(8) == 8
    assert bsi.pad_depth(9) == 16
    assert bsi.pad_depth(16) == 16
    assert bsi.pad_depth(17) == 24


def test_validate_field():
    with pytest.raises(bsi.BSIError):
        bsi.validate_field("v", 10, -10)  # min > max
    with pytest.raises(bsi.BSIError):
        bsi.validate_field("v", 0, 1 << 63)  # too deep
    bsi.validate_field("v", -5, 5)


def test_pred_row_packing():
    row = bsi.pred_row(-0b1011, 8)
    assert [int(row[k]) for k in range(8)] == [1, 1, 0, 1, 0, 0, 0, 0]
    assert int(row[8]) == 1  # sign flag
    assert int(bsi.pred_row(0b1011, 8)[8]) == 0


@pytest.mark.parametrize(
    "op,value,want",
    [
        ("gt", 1000, ("gt", 255)),   # empty
        ("le", 1000, ("le", 255)),   # everything valued
        ("eq", 1000, ("gt", 255)),   # empty
        ("ne", 1000, ("le", 255)),   # everything valued
        ("lt", -1000, ("lt", -255)),  # empty
        ("ge", -1000, ("ge", -255)),  # everything valued
        ("eq", -1000, ("lt", -255)),  # empty
        ("lt", 100, ("lt", 100)),    # in range: untouched
    ],
)
def test_clamp_predicate(op, value, want):
    assert bsi.clamp_predicate(op, value, 8) == want


def test_field_view_name():
    f = bsi.BSIField(name="qty", min=-5, max=300)
    assert f.view == "field_qty"
    assert f.bit_depth == 9
    assert bsi.is_field_view("field_qty")
    assert not bsi.is_field_view("standard")


# ---------------------------------------------------------------------------
# schema + import on a Holder
# ---------------------------------------------------------------------------


@pytest.fixture
def holder(tmp_path):
    h = Holder(str(tmp_path / "h"))
    h.open()
    yield h
    h.close()


def _mkfield(holder, lo=-1000, hi=1000, name="v"):
    idx = holder.create_index_if_not_exists("i")
    f = idx.create_frame_if_not_exists("f")
    f.set_options(range_enabled=True)
    if f.bsi_field(name) is None:
        f.create_field(name, lo, hi)
    return f


def test_field_requires_range_enabled(holder):
    idx = holder.create_index_if_not_exists("i")
    f = idx.create_frame_if_not_exists("f")
    with pytest.raises(FrameError):
        f.create_field("v", 0, 10)


def test_field_persists_across_reopen(holder, tmp_path):
    _mkfield(holder, -7, 300)
    holder.close()
    h2 = Holder(str(tmp_path / "h"))
    h2.open()
    try:
        f = h2.frame("i", "f")
        assert f.range_enabled
        fld = f.bsi_field("v")
        assert (fld.min, fld.max, fld.bit_depth) == (-7, 300, 9)
        assert f.schema_dict()["fields"] == [
            {"name": "v", "type": "int", "min": -7, "max": 300}
        ]
    finally:
        h2.close()


def test_import_value_out_of_range_rejected(holder):
    f = _mkfield(holder, -10, 10)
    with pytest.raises(bsi.BSIError):
        f.import_value("v", [1], [11])
    with pytest.raises(bsi.BSIError):
        f.import_value("v", [1], [-11])
    with pytest.raises(FrameError):
        f.import_value("nope", [1], [1])


def test_import_value_overwrites(holder):
    f = _mkfield(holder)
    ex = Executor(holder)
    f.import_value("v", [5, 9], [1000, -1000])
    (s,) = ex.execute("i", parse_string("Sum(frame=f, field=v)"), None, {})
    assert (s.value, s.count) == (0, 2)
    # Overwrite must clear stale magnitude/sign bits, not OR over them.
    f.import_value("v", [5], [-1])
    f.import_value("v", [9], [3])
    (s,) = ex.execute("i", parse_string("Sum(frame=f, field=v)"), None, {})
    assert (s.value, s.count) == (2, 2)
    (mn,) = ex.execute("i", parse_string("Min(frame=f, field=v)"), None, {})
    assert (mn.value, mn.count) == (-1, 1)
    (mx,) = ex.execute("i", parse_string("Max(frame=f, field=v)"), None, {})
    assert (mx.value, mx.count) == (3, 1)


# ---------------------------------------------------------------------------
# property test: randomized equivalence vs a per-column NumPy reference
# ---------------------------------------------------------------------------


def _rand_data(rng, lo, hi, n, n_slices):
    cols = rng.choice(n_slices * SLICE_WIDTH, size=n, replace=False)
    vals = rng.integers(lo, hi + 1, size=n)
    # Force the declared boundaries (and 0 when representable) into
    # every draw so edge magnitudes are always exercised.
    vals[0], vals[1] = lo, hi
    if lo <= 0 <= hi and n > 2:
        vals[2] = 0
    return cols.astype(np.int64), vals.astype(np.int64)


@pytest.mark.parametrize("use_coalescer", [False, True])
@pytest.mark.parametrize(
    "lo,hi",
    [(-1000, 1000), (0, 255), (-4, 3), (-(1 << 33), 1 << 33)],
)
def test_bsi_matches_numpy_reference(holder, lo, hi, use_coalescer):
    rng = np.random.default_rng(hash((lo, hi)) % (1 << 32))
    f = _mkfield(holder, lo, hi)
    cols, vals = _rand_data(rng, lo, hi, 500, 3)
    f.import_value("v", cols, vals)
    ref = dict(zip(cols.tolist(), vals.tolist()))

    co = CoalesceScheduler() if use_coalescer else None
    ex = Executor(holder, coalescer=co)
    try:
        def run(q):
            return ex.execute("i", parse_string(q), None, {})[0]

        preds = sorted(
            {lo, hi, lo - 1, hi + 1, 0, 1, -1, (lo + hi) // 2,
             int(vals[7]), int(vals[11])}
        )
        for op, pyop in OPS.items():
            for p in preds:
                got = run(f"Count(Range(frame=f, v {op} {p}))")
                want = sum(1 for v in ref.values() if pyop(v, p))
                assert got == want, (op, p, got, want)
        for a, b in [(lo, hi), (-1, 1), (0, 0), (5, 2), (lo - 99, hi + 99)]:
            got = run(f"Count(Range(frame=f, v >< [{a}, {b}]))")
            want = sum(1 for v in ref.values() if a <= v <= b)
            assert got == want, (a, b, got, want)

        s = run("Sum(frame=f, field=v)")
        assert (s.value, s.count) == (sum(ref.values()), len(ref))
        mn, mx = run("Min(frame=f, field=v)"), run("Max(frame=f, field=v)")
        vmin, vmax = min(ref.values()), max(ref.values())
        assert (mn.value, mn.count) == (
            vmin, sum(1 for v in ref.values() if v == vmin))
        assert (mx.value, mx.count) == (
            vmax, sum(1 for v in ref.values() if v == vmax))

        # filtered Sum: only columns matching the child bitmap count
        s = run("Sum(Range(frame=f, v > 0), frame=f, field=v)")
        pos = [v for v in ref.values() if v > 0]
        assert (s.value, s.count) == (sum(pos), len(pos))

        # composability inside set algebra
        got = run("Count(Intersect(Range(frame=f, v >= 0), Range(frame=f, v <= 1)))")
        assert got == sum(1 for v in ref.values() if 0 <= v <= 1)
    finally:
        ex.close()
        if co is not None:
            co.close()


def test_bsi_coalesced_storm_byte_identical(holder):
    rng = np.random.default_rng(3)
    f = _mkfield(holder)
    cols, vals = _rand_data(rng, -1000, 1000, 800, 2)
    f.import_value("v", cols, vals)
    co = CoalesceScheduler()
    ex = Executor(holder, coalescer=co)
    ex_direct = Executor(holder)
    queries = [
        "Count(Range(frame=f, v > 10))",
        "Sum(frame=f, field=v)",
        "Min(frame=f, field=v)",
        "Max(frame=f, field=v)",
        "Count(Range(frame=f, v >< [-100, 100]))",
    ]
    try:
        want = {
            q: ex_direct.execute("i", parse_string(q), None, {})[0]
            for q in queries
        }
        results = {}

        def run(i, q):
            results[i] = ex.execute("i", parse_string(q), None, {})[0]

        ts = [
            threading.Thread(target=run, args=(i, queries[i % len(queries)]))
            for i in range(20)
        ]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        for i, r in results.items():
            assert r == want[queries[i % len(queries)]], i
    finally:
        ex.close()
        ex_direct.close()
        co.close()


# ---------------------------------------------------------------------------
# program sharing per depth bucket
# ---------------------------------------------------------------------------


def test_same_bucket_fields_share_compiled_programs(holder):
    """Two fields of depths 3 and 7 share the depth-8 bucket: after the
    first field's query compiles an op kind, the second field's SAME op
    adds no compiled-program cache entry (the exec.programCache.entries
    gauge stays flat) — a new predicate VALUE doesn't either.  (The
    leaf-batch gather is keyed by the shape of the planes it reads, 8
    rows and 16 here, and has its own bound: test_plane_gather.py.)"""
    idx = holder.create_index_if_not_exists("i")
    f = idx.create_frame_if_not_exists("f")
    f.set_options(range_enabled=True)
    f.create_field("a", 0, 7)    # depth 3 -> bucket 8
    f.create_field("b", -100, 100)  # depth 7 -> bucket 8
    f.import_value("a", [1, 2, 3], [1, 5, 7])
    f.import_value("b", [1, 2, 3], [-5, 0, 99])
    ex = Executor(holder)
    run = lambda q: ex.execute("i", parse_string(q), None, {})[0]  # noqa: E731

    def entries():
        stats = plan.program_cache_stats()
        return stats["total"] - stats["bitplane.gatherPlanes"]

    assert run("Count(Range(frame=f, a > 2))") == 2
    warm = entries()
    assert run("Count(Range(frame=f, b > 2))") == 1  # same op, other field
    assert run("Count(Range(frame=f, b > -7))") == 3  # new predicate value
    assert entries() == warm

    (s,) = [run("Sum(frame=f, field=a)")]
    assert (s.value, s.count) == (13, 3)
    warm = entries()
    (s,) = [run("Sum(frame=f, field=b)")]
    assert (s.value, s.count) == (94, 3)
    assert entries() == warm
    ex.close()


# ---------------------------------------------------------------------------
# 2-node cluster: fan-out, import-value replication, partial reduce
# ---------------------------------------------------------------------------


@pytest.fixture
def two_servers(tmp_path):
    from pilosa_tpu.cluster import broadcast as bc
    from pilosa_tpu.cluster.topology import Cluster
    from pilosa_tpu.net.server import Server

    recv0, recv1 = bc.HTTPBroadcastReceiver(), bc.HTTPBroadcastReceiver()
    b0, b1 = bc.HTTPBroadcaster([]), bc.HTTPBroadcaster([])
    s0 = Server(
        data_dir=str(tmp_path / "n0"),
        cluster=Cluster(replica_n=1),
        broadcaster=b0,
        broadcast_receiver=recv0,
        anti_entropy_interval=3600,
        polling_interval=3600,
        cache_flush_interval=3600,
    )
    s1 = Server(
        data_dir=str(tmp_path / "n1"),
        cluster=Cluster(replica_n=1),
        broadcaster=b1,
        broadcast_receiver=recv1,
        anti_entropy_interval=3600,
        polling_interval=3600,
        cache_flush_interval=3600,
    )
    s0.open()
    s1.open()
    b0.internal_hosts.append(recv1.bound_host)
    b1.internal_hosts.append(recv0.bound_host)
    for c in (s0.cluster, s1.cluster):
        for host in sorted([s0.host, s1.host]):
            if c.node_by_host(host) is None:
                c.add_node(host)
        c.nodes.sort(key=lambda n: n.host)
    yield s0, s1
    s0.close()
    s1.close()


def test_two_node_bsi(two_servers):
    from pilosa_tpu.net.client import InternalClient

    s0, s1 = two_servers
    c0 = InternalClient(s0.host, timeout=10.0)
    c1 = InternalClient(s1.host, timeout=10.0)
    c0.create_index("i")
    c0.create_frame("i", "f", {"rangeEnabled": True})
    c0.create_field("i", "f", "v", -1000, 1000)
    # field fan-out reached the peer (and enabled range there)
    assert c1.frame_fields("i", "f") == [
        {"name": "v", "type": "int", "min": -1000, "max": 1000}
    ]

    rng = np.random.default_rng(11)
    n_slices = 4
    cols = rng.choice(n_slices * SLICE_WIDTH, size=600, replace=False)
    vals = rng.integers(-1000, 1001, size=600)
    vals[0], vals[1] = -1000, 1000
    by_slice = {}
    for c, v in zip(cols.tolist(), vals.tolist()):
        by_slice.setdefault(c // SLICE_WIDTH, []).append((c, v))
    for s, pairs in sorted(by_slice.items()):
        c0.import_value(
            "i", "f", "v", s, [c for c, _ in pairs], [v for _, v in pairs]
        )

    deadline = time.time() + 5.0
    while time.time() < deadline:
        if (
            s0.holder.index("i").max_slice() == n_slices - 1
            and s1.holder.index("i").max_slice() == n_slices - 1
        ):
            break
        time.sleep(0.02)

    ref = dict(zip(cols.tolist(), vals.tolist()))
    # both slices owned by each node contribute; partials reduce on the
    # coordinator — and BOTH coordinators agree.
    for client in (c0, c1):
        got = client.execute_pql("i", "Count(Range(frame=f, v > 100))")
        assert got == sum(1 for v in ref.values() if v > 100)
        got = client.execute_pql("i", "Count(Range(frame=f, v >< [-50, 50]))")
        assert got == sum(1 for v in ref.values() if -50 <= v <= 50)
    # aggregates over JSON (ValCount renders {"value","count"})
    st, data = c0._request(
        "POST", "/index/i/query", body=b"Sum(frame=f, field=v)"
    )
    assert st == 200
    assert json.loads(data)["results"][0] == {
        "value": int(sum(ref.values())),
        "count": len(ref),
    }
    vmin, vmax = min(ref.values()), max(ref.values())
    st, data = c1._request(
        "POST", "/index/i/query", body=b"Min(frame=f, field=v)"
    )
    assert json.loads(data)["results"][0] == {
        "value": vmin,
        "count": sum(1 for v in ref.values() if v == vmin),
    }
    st, data = c1._request(
        "POST", "/index/i/query", body=b"Max(frame=f, field=v)"
    )
    assert json.loads(data)["results"][0] == {
        "value": vmax,
        "count": sum(1 for v in ref.values() if v == vmax),
    }

    # the program-cache gauge is served on /metrics
    st, data = c0._request("GET", "/metrics")
    assert st == 200
    text = data.decode()
    assert "pilosa_exec_programCache_entries" in text

    # field delete fans out too
    c1.delete_field("i", "f", "v")
    assert c0.frame_fields("i", "f") == []


def test_import_value_validation(two_servers):
    from pilosa_tpu.net.client import ClientError, InternalClient

    s0, _ = two_servers
    c0 = InternalClient(s0.host, timeout=10.0)
    c0.create_index("i")
    c0.create_frame("i", "f", {"rangeEnabled": True})
    c0.create_field("i", "f", "v", 0, 100)
    with pytest.raises(ClientError):
        c0.import_value("i", "f", "v", 0, [1], [101])  # out of range
    with pytest.raises(ClientError):
        c0.import_value("i", "f", "nope", 0, [1], [1])  # unknown field


def test_executor_schema_errors(holder):
    idx = holder.create_index_if_not_exists("i")
    idx.create_frame_if_not_exists("f")  # NOT range-enabled
    ex = Executor(holder)
    with pytest.raises(ExecutorError):
        ex.execute("i", parse_string("Count(Range(frame=f, v > 1))"), None, {})
    with pytest.raises(ExecutorError):
        ex.execute("i", parse_string("Sum(frame=f, field=v)"), None, {})
    f = idx.frame("f")
    f.set_options(range_enabled=True)
    with pytest.raises(ExecutorError):  # unknown field
        ex.execute("i", parse_string("Count(Range(frame=f, v > 1))"), None, {})
    ex.close()
