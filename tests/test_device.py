"""HBM residency manager tests (pilosa_tpu/device/).

Pool unit tier: byte accounting, LRU victim order, pin leases, the
non-blocking-callback contract.  Integration tier: fragments and the
executor under a budget below total plane bytes — the ISSUE acceptance
scenario (query sweep over more fragments than fit completes correctly,
evictions happen, accounted residency never exceeds budget) — plus the
pending-point-write eviction coherence regression and the /debug/hbm
endpoint on a live server.
"""

import json

import numpy as np
import pytest

from pilosa_tpu import device as device_mod
from pilosa_tpu.cluster.topology import new_cluster
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.device.pool import PlanePool
from pilosa_tpu.exec.executor import Executor
from pilosa_tpu.ops import bitplane as bp
from pilosa_tpu.pql.parser import parse_string

MiB = 1 << 20


@pytest.fixture
def fresh_pool():
    """Swap a fresh global pool in for the test (fragments and the
    executor register with the process-global one)."""
    p = PlanePool()
    prev = device_mod._set_pool(p)
    yield p
    device_mod._set_pool(prev)


def budgeted_pool(budget):
    p = PlanePool(budget_bytes=budget)
    return p


# ---------------------------------------------------------------------------
# pool unit tier
# ---------------------------------------------------------------------------


class TestPlanePool:
    def make_entry(self, pool, key, nbytes, dev="dev0", evicted=None):
        def evict():
            if evicted is not None:
                evicted.append((key,))
            return True

        pool.admit((key,), {dev: nbytes}, evict, category="mirror",
                   info={"fragment": key})

    def test_accounting_and_lru_eviction(self):
        pool = budgeted_pool(10 * MiB)
        gone = []
        for i in range(5):
            self.make_entry(pool, f"e{i}", 2 * MiB, evicted=gone)
        assert pool.resident_bytes("dev0") == 10 * MiB
        assert gone == []
        # 6th entry exceeds the budget: the OLDEST entry goes.
        self.make_entry(pool, "e5", 2 * MiB, evicted=gone)
        assert gone == [("e0",)]
        assert pool.resident_bytes("dev0") == 10 * MiB
        assert pool.evictions == 1
        # Touch e1 (now oldest) and admit again: e2 is the victim.
        pool.touch(("e1",))
        self.make_entry(pool, "e6", 2 * MiB, evicted=gone)
        assert gone == [("e0",), ("e2",)]
        # The high-water mark never exceeded budget.
        assert pool.max_resident_bytes("dev0") <= 10 * MiB

    def test_per_device_budgets_are_independent(self):
        pool = budgeted_pool(4 * MiB)
        gone = []
        self.make_entry(pool, "a0", 3 * MiB, dev="devA", evicted=gone)
        self.make_entry(pool, "b0", 3 * MiB, dev="devB", evicted=gone)
        # devB is full but devA has room: only devA entries may be
        # evicted for a devA admission.
        self.make_entry(pool, "a1", 3 * MiB, dev="devA", evicted=gone)
        assert gone == [("a0",)]
        assert pool.resident_bytes("devB") == 3 * MiB

    def test_pinned_entries_never_evicted(self):
        pool = budgeted_pool(4 * MiB)
        gone = []
        self.make_entry(pool, "pinned", 3 * MiB, evicted=gone)
        assert pool.pin(("pinned",))
        for i in range(3):
            self.make_entry(pool, f"f{i}", 3 * MiB, evicted=gone)
        assert ("pinned",) not in gone
        snap = pool.snapshot()
        assert snap["counters"]["overBudget"] > 0  # breach counted, not hidden
        pool.unpin(("pinned",))
        self.make_entry(pool, "final", 3 * MiB, evicted=gone)
        assert ("pinned",) in gone

    def test_refusing_callback_is_skipped(self):
        pool = budgeted_pool(4 * MiB)
        pool.admit(("busy",), {"dev0": 3 * MiB}, lambda: False)
        gone = []
        self.make_entry(pool, "next", 3 * MiB, evicted=gone)
        # The refusing entry stays registered; the breach is counted.
        assert pool.contains(("busy",))
        snap = pool.snapshot()
        assert snap["counters"]["evictSkipped"] >= 1

    def test_resize_and_remove(self):
        pool = budgeted_pool(0)  # unbounded
        pool.admit(("k",), {"dev0": 4 * MiB}, lambda: True, category="sparse")
        pool.resize(("k",), {"dev0": 1 * MiB})
        assert pool.resident_bytes("dev0") == 1 * MiB
        pool.remove(("k",))
        assert pool.resident_bytes("dev0") == 0

    def test_pin_lease_context(self):
        pool = budgeted_pool(0)
        pool.admit(("k",), {"dev0": MiB}, lambda: True)
        with pool.pinned(("k",), None, ("missing",)):
            snap = pool.snapshot()
            (dev,) = snap["devices"]
            assert dev["pinned_bytes"] == MiB
        assert pool.snapshot()["devices"][0]["pinned_bytes"] == 0

    def test_cache_bytes_gauge_tracks_cache_category(self):
        pool = budgeted_pool(0)
        pool.admit(("m",), {"d": 2 * MiB}, lambda: True, category="mirror")
        pool.admit(("c",), {"d": 3 * MiB}, lambda: True, category="cache")
        assert pool.snapshot()["cache_bytes"] == 3 * MiB
        pool.remove(("c",))
        assert pool.snapshot()["cache_bytes"] == 0


# ---------------------------------------------------------------------------
# fragment integration
# ---------------------------------------------------------------------------


@pytest.fixture
def holder(tmp_path):
    h = Holder(str(tmp_path / "data"))
    h.open()
    yield h
    h.close()


def fill_fragments(holder, n_frags, rows_per_frag=2):
    """One fragment per slice with ``rows_per_frag`` distinct rows set."""
    idx = holder.create_index_if_not_exists("i")
    f = idx.create_frame_if_not_exists("f")
    for s in range(n_frags):
        for r in range(rows_per_frag):
            f.set_bit("standard", r, s * bp.SLICE_WIDTH + r + 1)
            # a column at the slice's end: the planes are of full width,
            # as wide as the rows of the leaf batches the budgets hold
            f.set_bit("standard", r, (s + 1) * bp.SLICE_WIDTH - 100 + r)
    return f


def frags_of(holder, n):
    v = holder.index("i").frame("f").view("standard")
    return [v.fragment(s) for s in range(n)]


class TestFragmentResidency:
    def test_mirror_registers_and_releases_on_close(self, holder, fresh_pool):
        fill_fragments(holder, 1)
        (frag,) = frags_of(holder, 1)
        frag.device_plane()
        assert fresh_pool.resident_bytes() == frag._plane.nbytes
        frag.close()
        assert fresh_pool.resident_bytes() == 0
        assert frag._device is None

    def test_eviction_under_budget_and_rebuild(self, holder, fresh_pool):
        """More mirrors than fit: LRU mirrors evict, every rebuilt plane
        stays correct, accounted residency never exceeds budget."""
        n = 24  # 3 fragments per virtual device (tests force 8 devices)
        fill_fragments(holder, n)
        frags = frags_of(holder, n)
        plane_bytes = frags[0]._plane.nbytes
        # Per-device budget of 2 planes, 3 planes homed per device.
        fresh_pool.configure(budget_bytes=2 * plane_bytes)
        for frag in frags:
            frag.device_plane()
        devs = {bp.home_device(f.slice) for f in frags}
        assert any(
            sum(1 for f in frags if bp.home_device(f.slice) == d) > 2
            for d in devs
        ), "scenario must oversubscribe at least one device"
        assert fresh_pool.evictions > 0
        for d in devs:
            assert fresh_pool.max_resident_bytes(d) <= 2 * plane_bytes
        # Evicted mirrors rebuild correctly on demand.
        for frag in frags:
            row = np.asarray(frag.device_row(0))
            cols = bp.np_row_to_columns(row).tolist()
            assert cols == [1, bp.SLICE_WIDTH - 100]

    def test_pending_point_write_survives_eviction(self, holder, fresh_pool):
        """Regression: point writes queued against a live mirror, then
        the mirror is evicted BEFORE the next read — the rebuilt plane
        must include the write and must NOT replay the stale pending
        scatter on top of it."""
        fill_fragments(holder, 1)
        (frag,) = frags_of(holder, 1)
        frag.device_plane()
        assert frag.set_bit(0, 7)  # queues a device-pending op
        assert frag._device_pending, "write should queue against the mirror"
        # Evict between the write and the next read.
        assert frag._evict_mirror()
        assert frag._device is None and not frag._device_pending
        cols = bp.np_row_to_columns(np.asarray(frag.device_row(0))).tolist()
        assert cols == [1, 7, bp.SLICE_WIDTH - 100]
        # And the same through pool pressure instead of a direct call:
        frag.device_plane()
        frag.set_bit(0, 9)
        dev = bp.home_device(frag.slice)
        fresh_pool.configure(budget_bytes=frag._plane.nbytes)
        fresh_pool.admit(
            ("hog",), {dev: frag._plane.nbytes}, lambda: True
        )
        assert frag._device is None, "budget pressure should evict the mirror"
        assert not frag._device_pending
        cols = bp.np_row_to_columns(np.asarray(frag.device_row(0))).tolist()
        assert cols == [1, 7, 9, bp.SLICE_WIDTH - 100]

    def test_pinned_mirror_survives_pressure(self, holder, fresh_pool):
        fill_fragments(holder, 1)
        (frag,) = frags_of(holder, 1)
        frag.device_plane()
        dev = bp.home_device(frag.slice)
        fresh_pool.configure(budget_bytes=frag._plane.nbytes)
        with fresh_pool.pinned(frag._pool_key):
            fresh_pool.admit(("hog",), {dev: frag._plane.nbytes}, lambda: True)
            assert frag._device is not None, "pinned plane must not drop"


# ---------------------------------------------------------------------------
# executor acceptance scenario (ISSUE: budget below total plane bytes)
# ---------------------------------------------------------------------------


class TestExecutorUnderBudget:
    def test_query_sweep_exceeding_budget(self, holder, fresh_pool):
        n = 24  # three fragments homed per virtual device
        fill_fragments(holder, n)
        frags = frags_of(holder, n)
        plane_bytes = frags[0]._plane.nbytes
        # Per-device budget below one device's three mirrors — and FAR
        # below the holder's total plane bytes.
        budget = int(2.5 * plane_bytes)
        assert budget * 8 < n * plane_bytes
        fresh_pool.configure(budget_bytes=budget)

        c = new_cluster(1)
        ex = Executor(
            holder,
            host=c.nodes[0].host,
            cluster=c,
            prefetcher=device_mod.Prefetcher(pool=fresh_pool),
        )
        try:
            # Per-slice sweep: TopN drives the HBM mirrors (the fused
            # scorer reads resident planes), Count checks exactness.
            for s in range(n):
                (pairs,) = ex.execute(
                    "i",
                    parse_string(
                        "TopN(Bitmap(rowID=0, frame=f), frame=f, n=2)"
                    ),
                    slices=[s],
                )
                got = {p.id: p.count for p in pairs}
                # row0 AND row0 = 2 bits; row1 AND row0 = 0 -> excluded
                assert got == {0: 2}
                (cnt,) = ex.execute(
                    "i",
                    parse_string("Count(Bitmap(rowID=1, frame=f))"),
                    slices=[s],
                )
                assert int(cnt) == 2
            # Sweep again so warm/cold paths both execute.
            for s in range(n):
                (cnt,) = ex.execute(
                    "i",
                    parse_string("Count(Bitmap(rowID=0, frame=f))"),
                    slices=[s],
                )
                assert int(cnt) == 2
        finally:
            ex.close()

        assert fresh_pool.evictions > 0, "sweep must exercise eviction"
        snap = fresh_pool.snapshot()
        for dev in snap["devices"]:
            assert dev["max_resident_bytes"] <= budget, (
                f"resident bytes exceeded budget on {dev['device']}"
            )

    def test_batch_cache_is_byte_evicted(self, holder, fresh_pool):
        """The executor's batch cache is bounded by the pool's BYTES,
        not just its entry count: a budget that fits one assembled
        batch but not two forces LRU eviction between query shapes."""
        fill_fragments(holder, 1)
        # One single-slice batch entry = 1 leaf row = 128 KiB; budget
        # holds one entry, not two.
        fresh_pool.configure(budget_bytes=192 * 1024)
        c = new_cluster(1)
        ex = Executor(holder, host=c.nodes[0].host, cluster=c)
        try:
            q1 = parse_string("Count(Bitmap(rowID=0, frame=f))")
            q2 = parse_string("Count(Bitmap(rowID=1, frame=f))")
            for _ in range(3):
                (n0,) = ex.execute("i", q1, slices=[0])
                (n1,) = ex.execute("i", q2, slices=[0])
                assert int(n0) == 2 and int(n1) == 2
            assert fresh_pool.evictions > 0
            with ex._batch_mu:
                assert len(ex._batch_cache) == 1, (
                    "pool bytes, not the count cap, should bound the cache"
                )
            snap = fresh_pool.snapshot()
            for dev in snap["devices"]:
                assert dev["max_resident_bytes"] <= 192 * 1024
        finally:
            ex.close()


# ---------------------------------------------------------------------------
# prefetcher
# ---------------------------------------------------------------------------


class TestPrefetcher:
    def test_prefetch_warms_cold_mirrors(self, holder, fresh_pool):
        n = 4
        fill_fragments(holder, n)
        frags = frags_of(holder, n)
        pf = device_mod.Prefetcher(pool=fresh_pool)
        scheduled = pf.prefetch(frags, wait=True)
        assert scheduled == n
        assert all(f._device is not None for f in frags)
        snap = fresh_pool.snapshot()
        assert snap["counters"]["prefetchMiss"] == n
        # Second pass: everything already resident.
        assert pf.prefetch(frags, wait=True) == 0
        assert fresh_pool.snapshot()["counters"]["prefetchHit"] == n


# ---------------------------------------------------------------------------
# GET /debug/hbm on a live server
# ---------------------------------------------------------------------------


def test_debug_hbm_endpoint(tmp_path, fresh_pool):
    from pilosa_tpu.net.client import InternalClient
    from pilosa_tpu.net.server import Server

    s = Server(
        data_dir=str(tmp_path / "data"),
        host="127.0.0.1:0",
        anti_entropy_interval=3600,
        polling_interval=3600,
        cache_flush_interval=3600,
        hbm_budget_bytes=64 * MiB,
    )
    s.open()
    try:
        client = InternalClient(s.host, timeout=10.0)
        client.create_index("i")
        client.create_frame("i", "f")
        client.execute_query("i", "SetBit(rowID=0, frame=f, columnID=3)", None)
        client.execute_query(
            "i", "TopN(Bitmap(rowID=0, frame=f), frame=f, n=1)", None
        )
        status, data = client._request("GET", "/debug/hbm")
        assert status == 200
        payload = json.loads(data)
        assert payload["budget_bytes"] == 64 * MiB
        assert payload["devices"], "a queried mirror must be resident"
        dev = payload["devices"][0]
        for field in (
            "device",
            "budget_bytes",
            "resident_bytes",
            "pinned_bytes",
            "max_resident_bytes",
            "entries",
        ):
            assert field in dev
        assert any(
            row.get("fragment") == "i/f/standard/0"
            for row in payload["fragments"]
        ), "per-fragment residency table must list the queried fragment"
        assert "evictions" in payload["counters"]
    finally:
        s.close()
    assert fresh_pool.resident_bytes() == 0, "server close releases HBM"


# ---------------------------------------------------------------------------
# mesh-sharded byte accounting (ISSUE 12: charge each device only its
# shard's bytes; per-shard residency visible in /debug/hbm)
# ---------------------------------------------------------------------------


class TestShardedAccounting:
    def _sharded(self, n_slices=8, words=256):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from pilosa_tpu.parallel import mesh as pmesh

        mesh = pmesh.default_slices_mesh()
        assert mesh is not None and mesh.devices.size == 8
        arr = np.zeros((n_slices, 2, words), dtype=np.uint32)
        return (
            jax.device_put(
                arr, NamedSharding(mesh, P(pmesh.AXIS_SLICES, None, None))
            ),
            arr.nbytes,
        )

    def test_sharded_array_charges_per_shard(self):
        sharded, nbytes = self._sharded()
        bbd = device_mod.bytes_by_device(sharded)
        assert len(bbd) == 8, "every mesh device owns a shard"
        assert all(n == nbytes // 8 for n in bbd.values()), bbd
        assert sum(bbd.values()) == nbytes

    def test_replicated_array_charges_full_copy_per_device(self):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from pilosa_tpu.parallel import mesh as pmesh

        mesh = pmesh.default_slices_mesh()
        arr = np.zeros((4, 16), dtype=np.uint32)
        rep = jax.device_put(arr, NamedSharding(mesh, P()))
        bbd = device_mod.bytes_by_device(rep)
        # Each device holds a FULL copy — an even split would
        # under-account 8x.
        assert len(bbd) == 8
        assert all(n == arr.nbytes for n in bbd.values())

    def test_sharded_entry_fits_per_device_budget(self, fresh_pool):
        """The regression the even/global attribution broke: a sharded
        array whose GLOBAL size exceeds the per-device budget — but
        whose per-shard share fits — must admit without evicting
        anything and without an over-budget breach."""
        sharded, nbytes = self._sharded()
        share = nbytes // 8
        fresh_pool.configure(budget_bytes=2 * share)  # global is 8x share
        fresh_pool.admit(
            ("resident",),
            {d: share for d in device_mod.bytes_by_device(sharded)},
            lambda: True,
            category="mirror",
        )
        fresh_pool.admit(
            ("batch",),
            device_mod.bytes_by_device(sharded),
            lambda: True,
            category="cache",
            info={"cache": "batch"},
        )
        snap = fresh_pool.snapshot()
        assert fresh_pool.evictions == 0
        assert snap["counters"]["overBudget"] == 0
        assert fresh_pool.contains(("resident",))
        for dev in snap["devices"]:
            assert dev["resident_bytes"] <= 2 * share
        # /debug/hbm surfaces the per-shard rows.
        batch_rows = [
            row
            for dev in snap["devices"]
            for row in dev["entries"]
            if row.get("cache") == "batch"
        ]
        assert len(batch_rows) == 8
        assert all(row["bytes"] == share for row in batch_rows)
        assert all(
            row.get("sharded") and row.get("shards") == 8
            for row in batch_rows
        )

    def test_executor_sharded_sweep_within_per_device_budget(
        self, holder, fresh_pool
    ):
        """End to end at an artificial per-device budget: an 8-slice
        mesh-sharded Count through the executor — mirrors land on their
        home shards, the assembled global batch charges per shard, and
        no device's reported residency exceeds its budget."""
        n = 8
        fill_fragments(holder, n)
        frags = frags_of(holder, n)
        plane_bytes = frags[0]._plane.nbytes
        # Mirror (1 plane) + the batch entry's shard + zero-row slack
        # fits; the GLOBAL batch (n x 2 leaves x 128 KiB) would not.
        budget = 2 * plane_bytes
        fresh_pool.configure(budget_bytes=budget)
        c = new_cluster(1)
        ex = Executor(holder, host=c.nodes[0].host, cluster=c)
        try:
            (cnt,) = ex.execute(
                "i",
                parse_string(
                    "Count(Intersect(Bitmap(rowID=0, frame=f),"
                    " Bitmap(rowID=1, frame=f)))"
                ),
                slices=list(range(n)),
            )
            assert int(cnt) == 0  # rows 0/1 share no columns per fixture
            (cnt1,) = ex.execute(
                "i",
                parse_string("Count(Bitmap(rowID=0, frame=f))"),
                slices=list(range(n)),
            )
            assert int(cnt1) == 2 * n
            snap = fresh_pool.snapshot()
            assert snap["counters"]["overBudget"] == 0
            assert fresh_pool.evictions == 0
            for dev in snap["devices"]:
                assert dev["max_resident_bytes"] <= budget, dev
        finally:
            ex.close()
