"""Device-fault tolerance tests (device/health.py + exec/hosteval.py).

The acceptance bar (ISSUE 15): classified launch failures drive the
healthy → suspect → quarantined state machine with half-open probes; a
quarantined accelerator answers BYTE-IDENTICALLY from the authoritative
host planes (Count/Bitmap algebra, BSI ± predicates, aggregates, TopN);
a coalesced launch failure fails over per-waiter without poisoning the
shared batch; a hung collective trips the launch watchdog instead of
wedging; detached coalesce waiters' batch errors are consumed, not
GC-logged; and an e2e two-node cluster with one node's device flapping
serves zero wrong answers, quarantines, heals through a probe, and
rejoins the device path.
"""

import concurrent.futures
import time
from contextlib import suppress

import jax
import numpy as np
import pytest

from pilosa_tpu.cluster.topology import Cluster, new_cluster
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.device import health as health_mod
from pilosa_tpu.device.health import (
    COLLECTIVE,
    KIND_ERROR,
    KIND_HANG,
    KIND_OOM,
    MODE_DENY,
    MODE_OK,
    MODE_PROBE,
    STATE_HEALTHY,
    STATE_QUARANTINED,
    STATE_SUSPECT,
    DeviceHealth,
    LaunchWatchdogTimeout,
)
from pilosa_tpu.exec import Executor, coalesce as coalesce_mod
from pilosa_tpu.exec.coalesce import CoalesceScheduler
from pilosa_tpu.net import resilience as rz
from pilosa_tpu.ops.bitplane import SLICE_WIDTH
from pilosa_tpu.pql.parser import parse_string
from pilosa_tpu.testing import faults


class _Stats:
    def __init__(self):
        self.counts: dict = {}

    def count(self, name, value=1, rate=1.0):
        self.counts[name] = self.counts.get(name, 0) + value

    def count_with_custom_tags(self, name, value, tags):
        key = name + "".join(f"[{t}]" for t in sorted(tags))
        self.counts[key] = self.counts.get(key, 0) + value

    def gauge(self, *a, **k):
        pass

    def histogram(self, *a, **k):
        pass


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


@pytest.fixture
def holder(tmp_path):
    h = Holder(str(tmp_path / "data"))
    h.open()
    yield h
    h.close()


# ---------------------------------------------------------------------------
# classification
# ---------------------------------------------------------------------------


def test_classify_kinds():
    assert health_mod.classify(LaunchWatchdogTimeout("x")) == KIND_HANG
    assert health_mod.classify(faults.FaultOOM("injected oom")) == KIND_OOM
    assert health_mod.classify(faults.FaultError("injected")) == KIND_ERROR
    assert (
        health_mod.classify(
            RuntimeError("RESOURCE_EXHAUSTED: out of memory")
        )
        == KIND_OOM
    )
    # Non-device exceptions must re-raise at the launch sites.
    assert health_mod.classify(ValueError("bad frame")) is None
    assert health_mod.classify(rz.DeadlineExceeded("budget")) is None
    assert health_mod.classify(KeyError("x")) is None


def test_classify_runtime_errors():
    from jax.errors import JaxRuntimeError

    assert health_mod.classify(JaxRuntimeError("INTERNAL: boom")) == KIND_ERROR
    assert (
        health_mod.classify(
            JaxRuntimeError(
                "RESOURCE_EXHAUSTED: Error allocating device buffer: "
                "Attempting to allocate 1.00G. That was not possible."
            )
        )
        == KIND_OOM
    )


def test_classify_propagates_lowering_and_compile_errors():
    """A program that does not lower or compile can never run here:
    serving it from the host planes would hide a broken device path, so
    the launch sites must re-raise it."""
    from jax.errors import JaxRuntimeError, TracerBoolConversionError

    # Tracing / lowering: ordinary Python exceptions, whatever module
    # their type lives in.
    try:
        jax.jit(lambda x: 1 if x > 0 else 0)(np.int32(1))
    except TracerBoolConversionError as e:
        assert health_mod.classify(e) is None
    else:
        raise AssertionError("expected a tracer error")
    assert (
        health_mod.classify(
            ValueError("The Pallas TPU lowering currently requires ...")
        )
        is None
    )
    assert health_mod.classify(NotImplementedError("no lowering")) is None
    # XLA / Mosaic compile failures arrive as JaxRuntimeError and say so
    # — an HBM overrun found at compile time included.
    for msg in (
        "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
        "memory in memory space hbm. Used 20.5G of 15.75G hbm.",
        "INTERNAL: Mosaic failed to compile TPU kernel: unsupported shape",
        "INVALID_ARGUMENT: during compilation: bad layout",
    ):
        assert health_mod.classify(JaxRuntimeError(msg)) is None, msg


# ---------------------------------------------------------------------------
# state machine
# ---------------------------------------------------------------------------


def test_quarantine_threshold_and_halfopen_probe_recovery():
    h = DeviceHealth(
        quarantine_threshold=2, open_ms=80, probe_successes=2, watchdog_ms=0
    )
    p = ["device:0"]
    assert h.acquire(p) == MODE_OK
    h.failure(p, KIND_ERROR)
    assert h.snapshot()["paths"]["device:0"]["state"] == STATE_SUSPECT
    assert h.acquire(p) == MODE_OK  # suspect still launches
    h.failure(p, KIND_ERROR)
    snap = h.snapshot()["paths"]["device:0"]
    assert snap["state"] == STATE_QUARANTINED
    assert h.degraded() and h.snapshot()["degraded"]
    assert h.acquire(p) == MODE_DENY
    time.sleep(0.1)
    # Past the open window: exactly ONE probe is admitted.
    assert h.acquire(p) == MODE_PROBE
    assert h.acquire(p) == MODE_DENY  # probe exclusive
    # Probe succeeds, but probe_successes=2: still quarantined, next
    # probe admitted immediately (no new open wait).
    h.success(p, probe=True)
    assert h.snapshot()["paths"]["device:0"]["state"] == STATE_QUARANTINED
    assert h.acquire(p) == MODE_PROBE
    h.success(p, probe=True)
    assert h.snapshot()["paths"]["device:0"]["state"] == STATE_HEALTHY
    assert h.acquire(p) == MODE_OK
    assert not h.degraded()


def test_failed_probe_rearms_quarantine_clock():
    h = DeviceHealth(quarantine_threshold=1, open_ms=60, watchdog_ms=0)
    p = ["device:0"]
    h.failure(p, KIND_OOM)
    assert h.acquire(p) == MODE_DENY
    time.sleep(0.08)
    assert h.acquire(p) == MODE_PROBE
    h.failure(p, KIND_OOM, probe=True)
    assert h.acquire(p) == MODE_DENY  # clock re-armed
    time.sleep(0.08)
    assert h.acquire(p) == MODE_PROBE


def test_hang_quarantines_immediately_and_success_resets_suspect():
    h = DeviceHealth(quarantine_threshold=5, open_ms=1000, watchdog_ms=0)
    p = ["device:0"]
    h.failure(p, KIND_ERROR)
    h.success(p)
    assert h.snapshot()["paths"]["device:0"]["state"] == STATE_HEALTHY
    assert h.snapshot()["paths"]["device:0"]["consecutiveFailures"] == 0
    h.failure(p, KIND_HANG)  # one hang is enough
    assert h.snapshot()["paths"]["device:0"]["state"] == STATE_QUARANTINED


def test_failure_with_fault_device_narrows_blame():
    h = DeviceHealth(quarantine_threshold=1, watchdog_ms=0)
    paths = ["device:0", "device:1"]
    h.failure(paths, KIND_ERROR, device=1)
    snap = h.snapshot()["paths"]
    assert snap["device:1"]["state"] == STATE_QUARANTINED
    assert "device:0" not in snap or snap["device:0"]["state"] == STATE_HEALTHY


def test_state_change_callback_fires_on_quarantine_and_heal():
    events = []
    h = DeviceHealth(
        quarantine_threshold=1,
        open_ms=40,
        watchdog_ms=0,
        on_state_change=lambda p, s: events.append((p, s)),
    )
    h.failure(["device:0"], KIND_ERROR)
    time.sleep(0.06)
    assert h.acquire(["device:0"]) == MODE_PROBE
    h.success(["device:0"], probe=True)
    assert events == [
        ("device:0", STATE_QUARANTINED),
        ("device:0", STATE_HEALTHY),
    ]


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------


def test_watchdog_trips_and_abandons_then_recovers():
    stats = _Stats()
    r = health_mod._WatchdogRunner(stats=stats)
    try:
        with pytest.raises(LaunchWatchdogTimeout):
            r.run(lambda: time.sleep(0.4) or "late", timeout_s=0.05)
        # A fresh runner serves the next call even while the old one
        # still sleeps.
        assert r.run(lambda: "ok", timeout_s=5.0) == "ok"
        time.sleep(0.45)
        assert stats.counts.get("device.watchdog.abandonedCompletions") == 1
    finally:
        r.close()


def test_run_collective_hang_trips_watchdog_and_quarantines_mesh_path():
    stats = _Stats()
    h = DeviceHealth(watchdog_ms=60, open_ms=50, stats=stats)
    try:
        with pytest.raises(LaunchWatchdogTimeout):
            h.run_collective(lambda: time.sleep(0.3))
        assert stats.counts.get("device.watchdogTrips") == 1
        assert (
            h.snapshot()["paths"][COLLECTIVE]["state"] == STATE_QUARANTINED
        )
        assert not h.collective_allowed()
        with pytest.raises(health_mod.CollectiveUnavailable):
            h.run_collective(lambda: "never runs")
        # Past the open window the next collective IS the probe; wait
        # out the abandoned sleeper so the lock is free again.
        time.sleep(0.3)
        assert h.collective_allowed()
        assert h.run_collective(lambda: 42) == 42
        assert h.snapshot()["paths"][COLLECTIVE]["state"] == STATE_HEALTHY
    finally:
        h.close()


def test_run_collective_error_counts_against_collective_path():
    h = DeviceHealth(watchdog_ms=0, quarantine_threshold=1)
    with pytest.raises(faults.FaultError):
        h.run_collective(lambda: (_ for _ in ()).throw(faults.FaultError("x")))
    assert h.snapshot()["paths"][COLLECTIVE]["state"] == STATE_QUARANTINED
    # Non-device exceptions propagate unrecorded.
    h2 = DeviceHealth(watchdog_ms=0, quarantine_threshold=1)
    with pytest.raises(ValueError):
        h2.run_collective(lambda: (_ for _ in ()).throw(ValueError("x")))
    assert COLLECTIVE not in h2.snapshot()["paths"] or (
        h2.snapshot()["paths"][COLLECTIVE]["state"] == STATE_HEALTHY
    )


# ---------------------------------------------------------------------------
# fault grammar (satellite: kind= + per-device matching)
# ---------------------------------------------------------------------------


def test_fault_kind_grammar_and_validation():
    plan = faults.parse("device.launch:kind=oom,times=1")
    with pytest.raises(faults.FaultOOM):
        plan.check("device.launch")
    plan.check("device.launch")  # times exhausted
    with pytest.raises(faults.FaultSpecError):
        faults.parse("device.launch:kind=frobnicate")
    with pytest.raises(faults.FaultSpecError):
        faults.parse("rpc.send:kind=oom")
    with pytest.raises(faults.FaultSpecError):
        faults.parse("rpc.recv:device=1")


def test_fault_per_device_matching():
    plan = faults.parse("device.launch:kind=error,device=3")
    plan.check("device.launch", device=2)  # no fire
    plan.check("device.launch")  # no device info: no fire
    with pytest.raises(faults.FaultError):
        plan.check("device.launch", device=3)
    assert plan.rules[0].hits == 1


def test_fault_hang_sleeps_then_returns():
    plan = faults.parse("device.launch:kind=hang,delay-ms=30,times=1")
    t0 = time.monotonic()
    plan.check("device.launch")  # returns (after the sleep), no raise
    assert time.monotonic() - t0 >= 0.025


# ---------------------------------------------------------------------------
# executor: host fallback byte-identity + quarantine/heal
# ---------------------------------------------------------------------------

BSI_MIN, BSI_MAX = -128, 127


def _seed(holder, rng):
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
    for col in range(0, 3 * SLICE_WIDTH, SLICE_WIDTH // 7):
        f.import_value("v", [col], [int(rng.integers(BSI_MIN, BSI_MAX + 1))])
    ft = idx.create_frame("t", cache_size=64)
    for row in range(6):
        for col in range(0, 2 * SLICE_WIDTH, SLICE_WIDTH // (5 + row)):
            ft.set_bit("standard", row, col)


MIXED = [
    "Count(Intersect(Bitmap(rowID=1, frame=f), Bitmap(rowID=2, frame=f)))",
    "Count(Union(Bitmap(rowID=1, frame=f), Bitmap(rowID=3, frame=f)))",
    "Count(Difference(Bitmap(rowID=2, frame=f), Bitmap(rowID=4, frame=f)))",
    "Bitmap(rowID=1, frame=f)",
    "Union(Bitmap(rowID=2, frame=f), Bitmap(rowID=3, frame=f))",
    f"Count(Range(frame=f, v > {BSI_MIN}))",
    f"Count(Range(frame=f, v <= {BSI_MAX}))",
    "Count(Range(frame=f, v == 0))",
    f"Count(Range(frame=f, v >< [{BSI_MIN}, {BSI_MAX}]))",
    "Count(Intersect(Bitmap(rowID=1, frame=f), Range(frame=f, v < -5)))",
    "Sum(frame=f, field=v)",
    "Sum(Bitmap(rowID=1, frame=f), frame=f, field=v)",
    "Min(frame=f, field=v)",
    "Max(frame=f, field=v)",
    "TopN(Bitmap(rowID=0, frame=t), frame=t, n=3)",
    "TopN(frame=t, n=2)",
]


def _canon(result):
    if hasattr(result, "bits"):
        return ("bits", tuple(result.bits()))
    if isinstance(result, list):
        return ("pairs", tuple((p.id, p.count) for p in result))
    if hasattr(result, "value"):
        return ("valcount", int(result.value), int(result.count))
    if result is None:
        return ("none",)
    return ("val", int(result))


def _run_all(ex, queries=MIXED):
    return [_canon(ex.execute("i", parse_string(q))[0]) for q in queries]


def test_quarantined_device_serves_byte_identical_from_host(holder, rng):
    _seed(holder, rng)
    c = new_cluster(1)
    host = c.nodes[0].host
    plain = Executor(holder, host=host, cluster=c)
    try:
        expected = _run_all(plain)
    finally:
        plain.close()

    dh = DeviceHealth(quarantine_threshold=1, open_ms=3600_000, watchdog_ms=0)
    ex = Executor(holder, host=host, cluster=c, device_health=dh)
    try:
        # Force full quarantine: every device path + the collective.
        dh.failure(dh.device_paths() + [COLLECTIVE], KIND_OOM)
        assert dh.degraded()
        got = _run_all(ex)
        assert got == expected
        # Still quarantined (open window is an hour): every answer above
        # came from the host evaluator.
        assert dh.degraded()
        assert (
            ex.holder.stats is not None
        )  # stats path exercised via hosteval counters
    finally:
        ex.close()
        dh.close()


def test_quarantined_topn_prepared_without_src_words_scores_on_the_host(
    holder, rng, monkeypatch
):
    """A folded TopN(src) whose fragments all hold the src in their own
    planes is prepared with no host copy of it (``build`` ``direct``);
    with the device denied the host scorer reads the src row where it
    reads the candidates, and the answer is the device's."""
    from pilosa_tpu.exec import hosteval as hosteval_mod

    _seed(holder, rng)
    text = "TopN(Bitmap(rowID=2, frame=t), frame=t, n=6)"
    c = new_cluster(1)
    host = c.nodes[0].host
    plain = Executor(holder, host=host, cluster=c)
    try:
        expected = _run_all(plain, [text])
    finally:
        plain.close()
    assert len(expected[0][1]) == 6

    built, scored = [], []
    real_build = Executor._topn_folded_build
    real_score = hosteval_mod.HostEvaluator.score_topn_parts

    def keep_build(self, *a):
        built.append(real_build(self, *a))
        return built[-1]

    def keep_score(self, parts):
        scored.append([p[2] for p in parts])
        return real_score(self, parts)

    monkeypatch.setattr(Executor, "_topn_folded_build", keep_build)
    monkeypatch.setattr(hosteval_mod.HostEvaluator, "score_topn_parts", keep_score)
    dh = DeviceHealth(quarantine_threshold=1, open_ms=3600_000, watchdog_ms=0)
    ex = Executor(holder, host=host, cluster=c, device_health=dh)
    try:
        dh.failure(dh.device_paths() + [COLLECTIVE], KIND_OOM)
        assert _run_all(ex, [text]) == expected
        assert [b["build"] for b in built] == ["direct"]
        # the host scorer was handed no src words, for either slice
        assert scored == [[None, None]]
    finally:
        ex.close()
        dh.close()


def test_persistent_fault_quarantines_then_heals_through_probe(holder, rng):
    _seed(holder, rng)
    c = new_cluster(1)
    host = c.nodes[0].host
    plain = Executor(holder, host=host, cluster=c)
    try:
        expected = _run_all(plain)
    finally:
        plain.close()

    dh = DeviceHealth(quarantine_threshold=2, open_ms=120, watchdog_ms=0)
    ex = Executor(holder, host=host, cluster=c, device_health=dh)
    try:
        faults.install("device.launch:mode=error")
        # Every query answers correctly despite the persistent fault
        # (retry -> failure -> host fallback), and the state machine
        # walks suspect -> quarantined.
        got = _run_all(ex)
        assert got == expected
        assert dh.degraded()
        # Clear the fault, wait out the open window: the next query IS
        # the half-open probe, succeeds on device, and heals the path.
        faults.clear()
        time.sleep(0.15)
        got = _run_all(ex)
        assert got == expected
        assert not dh.degraded()
        snap = ex.device_health.snapshot()
        assert snap["paths"]["device:0"]["state"] == STATE_HEALTHY
        assert snap["paths"]["device:0"]["quarantines"] >= 1
    finally:
        ex.close()
        dh.close()


def test_coalesced_fault_fails_over_per_waiter(holder, rng):
    """A persistent fault under a CONCURRENT distinct-query storm
    through the coalescer: every waiter fails over to the host path
    independently — zero wrong answers — and the shared scheduler keeps
    serving cleanly after the fault clears."""
    _seed(holder, rng)
    c = new_cluster(1)
    host = c.nodes[0].host
    plain = Executor(holder, host=host, cluster=c)
    try:
        expected = _run_all(plain)
    finally:
        plain.close()

    dh = DeviceHealth(quarantine_threshold=3, open_ms=100, watchdog_ms=0)
    co = CoalesceScheduler(max_wait_us=100_000, health=dh)
    ex = Executor(holder, host=host, cluster=c, coalescer=co, device_health=dh)
    try:
        faults.install("device.launch:mode=error")

        def run_mix(t):
            order = list(range(t, len(MIXED))) + list(range(t))
            got = [None] * len(MIXED)
            for i in order:
                got[i] = _canon(ex.execute("i", parse_string(MIXED[i]))[0])
            return got

        with concurrent.futures.ThreadPoolExecutor(6) as pool:
            for got in pool.map(run_mix, range(6)):
                assert got == expected
        assert dh.degraded()
        faults.clear()
        time.sleep(0.13)
        assert _run_all(ex) == expected
        assert not dh.degraded()
    finally:
        ex.close()
        co.close()
        dh.close()


# ---------------------------------------------------------------------------
# abandoned-waiter error consumption (satellite bugfix)
# ---------------------------------------------------------------------------


def test_abandoned_coalesce_error_is_consumed_and_counted():
    stats = _Stats()
    co = CoalesceScheduler(max_wait_us=0)
    try:
        # A float batch makes the shared launch's popcount fail AFTER
        # submission — the shape of a batch error landing once every
        # waiter has detached on deadline expiry.
        batch = np.zeros((2, 2, 8), dtype=np.float32)
        fut = co.submit(
            ("Intersect", ("leaf", 0), ("leaf", 1)), "count", batch
        )
        # The waiter detaches (deadline): it consumes the eventual
        # error via the done-callback instead of ever calling result().
        fut.add_done_callback(coalesce_mod.consume_abandoned(stats))
        deadline = time.monotonic() + 10
        while not fut.done() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert fut.done()
        assert stats.counts.get("exec.coalesce.abandonedErrors") == 1
        # The exception WAS retrieved: the future's GC path will not
        # log "exception was never retrieved".
        assert fut.exception(timeout=0) is not None
    finally:
        co.close()


# ---------------------------------------------------------------------------
# degraded-replica deprioritization
# ---------------------------------------------------------------------------


def test_slices_by_node_prefers_non_degraded_replica(tmp_path):
    h = Holder(str(tmp_path / "d"))
    h.open()
    try:
        cluster = Cluster(replica_n=2)
        cluster.add_node("127.0.0.1:1")
        cluster.add_node("127.0.0.1:2")
        h.create_index("i")
        ex = Executor(h, host="127.0.0.1:1", cluster=cluster)
        try:
            slices = [0, 1, 2, 3]
            base = ex._slices_by_node(cluster.nodes, "i", slices)
            # With replicas=2 both nodes own every slice; the primary
            # wins by default, so both hosts normally appear.
            assert sum(len(v[1]) for v in base.values()) == len(slices)
            # Degrade node 1: everything routes to node 2 (the healthy
            # replica), and the health version bump invalidates the
            # routing cache.
            assert cluster.note_degraded("127.0.0.1:1", True)
            m = ex._slices_by_node(cluster.nodes, "i", slices)
            assert set(m) == {"127.0.0.1:2"}
            # Both degraded: fall back to primary-order routing.
            assert cluster.note_degraded("127.0.0.1:2", True)
            m = ex._slices_by_node(cluster.nodes, "i", slices)
            assert m.keys() == base.keys()
            # Healing flips back.
            assert cluster.note_degraded("127.0.0.1:1", False)
            assert cluster.note_degraded("127.0.0.1:2", False)
            m = ex._slices_by_node(cluster.nodes, "i", slices)
            assert m.keys() == base.keys()
            assert not cluster.note_degraded("127.0.0.1:2", False)  # no-op
        finally:
            ex.close()
    finally:
        h.close()


# ---------------------------------------------------------------------------
# e2e: two nodes, one device flapping
# ---------------------------------------------------------------------------


def _two_servers(tmp_path):
    from pilosa_tpu.net.server import Server
    import socket

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    hosts = sorted(f"127.0.0.1:{free_port()}" for _ in range(2))
    kw = dict(
        anti_entropy_interval=3600,
        polling_interval=3600,
        cache_flush_interval=3600,
        query_timeout_ms=30_000.0,
        retry_attempts=1,
        quarantine_threshold=2,
        quarantine_open_ms=200.0,
        launch_watchdog_ms=0.0,
        admission=False,
    )

    def make(name, host):
        cluster = Cluster(replica_n=1)
        s = Server(
            data_dir=str(tmp_path / name), host=host, cluster=cluster, **kw
        )
        s.open()
        for hh in hosts:
            if cluster.node_by_host(hh) is None:
                cluster.add_node(hh)
        cluster.nodes.sort(key=lambda n: n.host)
        return s

    s0, s1 = make("n0", hosts[0]), make("n1", hosts[1])
    for s in (s0, s1):
        s.holder.create_index_if_not_exists("i")
        s.holder.index("i").create_frame_if_not_exists("f")
    return s0, s1


@pytest.mark.slow
def test_e2e_two_node_storm_with_flapping_device(tmp_path):
    """One node's device flaps under a mixed storm: zero wrong answers
    (the degraded node serves via host fallback), its /debug/health
    shows the quarantine, it heals after the fault clears, and rejoins
    the device path."""
    import json

    from pilosa_tpu.net.client import InternalClient

    s0, s1 = _two_servers(tmp_path)
    try:
        n_slices = 4
        for sl in range(n_slices):
            owner = s0.cluster.fragment_nodes("i", sl)[0].host
            srv = s0 if owner == s0.host else s1
            for row in (1, 2):
                srv.holder.frame("i", "f").set_bit(
                    "standard", row, sl * SLICE_WIDTH + row
                )
            srv.holder.frame("i", "f").set_bit(
                "standard", 1, sl * SLICE_WIDTH + 7
            )
        for s in (s0, s1):
            s.holder.index("i").set_remote_max_slice(n_slices - 1)

        queries = [
            "Count(Bitmap(rowID=1, frame=f))",
            "Count(Union(Bitmap(rowID=1, frame=f), Bitmap(rowID=2, frame=f)))",
            "Count(Intersect(Bitmap(rowID=1, frame=f), Bitmap(rowID=2, frame=f)))",
        ]
        c1 = InternalClient(s1.host, timeout=15.0)
        c0 = InternalClient(s0.host, timeout=15.0)

        def health(client):
            status, data = client._request("GET", "/debug/health")
            assert status == 200
            return json.loads(data)

        want = [c1.execute_pql("i", q) for q in queries]
        assert want[0] == 2 * n_slices

        # Flap node 0's device only: every query through the healthy
        # coordinator must stay byte-identical while node 0 degrades.
        faults.install(f"device.launch:mode=error,host={s0.host}")
        for _round in range(4):
            got = [c1.execute_pql("i", q) for q in queries]
            assert got == want
        snap0 = health(c0)
        assert snap0["device"]["degraded"] is True
        states = {
            p: st["state"] for p, st in snap0["device"]["paths"].items()
        }
        assert STATE_QUARANTINED in states.values()
        # The healthy node never degraded.
        assert health(c1)["device"]["degraded"] is False

        # Heal: clear the fault, wait out the open window; the next
        # query through node 0 is the half-open probe.
        faults.clear()
        time.sleep(0.25)
        got = [c1.execute_pql("i", q) for q in queries]
        assert got == want
        snap0 = health(c0)
        assert snap0["device"]["degraded"] is False
    finally:
        faults.clear()
        with suppress(Exception):
            s0.close()
        with suppress(Exception):
            s1.close()
