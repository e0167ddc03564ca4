"""Where a request's wall time goes on the host: what a span says of
the time its thread stood blocked on purpose (``blocked_ms`` by kind),
the contended lock of the residency pool, the dispatcher's account of
its own life, and what the remainder — a thread that wanted to run and
did not — means: one process reads itself alone and beside eight
threads that spin pure Python."""

import concurrent.futures
import json
import os
import socket
import statistics
import sys
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from pilosa_tpu.cluster.topology import Cluster
from pilosa_tpu.device.pool import PlanePool
from pilosa_tpu.exec.coalesce import CoalesceScheduler, await_result
from pilosa_tpu.net.client import InternalClient
from pilosa_tpu.net.server import Server
from pilosa_tpu.obs import stats as stats_mod
from pilosa_tpu.obs import trace
from pilosa_tpu.ops.bitplane import SLICE_WIDTH

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import metrics  # noqa: E402 — benchmarks/metrics.py

QUIET = dict(anti_entropy_interval=3600, polling_interval=3600,
             cache_flush_interval=3600)


# ---------------------------------------------------------------------------
# blocked_ms: a span, its parent, a sibling, another thread
# ---------------------------------------------------------------------------


def _traced(tr, body):
    root = tr.start_trace("query")
    token = root.activate()
    try:
        body()
    finally:
        root.deactivate(token)
    return tr.finish_root(root)


def test_a_span_says_how_long_its_thread_was_blocked_and_of_what_kind():
    tr = trace.Tracer()
    fut: concurrent.futures.Future = concurrent.futures.Future()

    def body():
        with tr.span("parent"):
            with tr.span("waits"):
                threading.Timer(0.05, fut.set_result, args=(7,)).start()
                with trace.blocked("queue"):
                    assert fut.result(timeout=30) == 7
            with tr.span("sibling"):
                pass

    by_name = {s["name"]: s for s in _traced(tr, body)["spans"]}
    for name in ("waits", "parent", "query"):
        s = by_name[name]
        # the timer's 50 ms, and whatever a busy machine adds to a wake-up
        assert 40 <= s["blocked_ms"] <= s["duration_ms"] + 0.01
        assert s["tags"]["blocked"] == {"queue": s["blocked_ms"]}
    assert by_name["waits"]["blocked_ms"] == by_name["parent"]["blocked_ms"]
    # opened after the wait: nothing of it
    assert by_name["sibling"]["blocked_ms"] == 0.0
    assert "blocked" not in by_name["sibling"]["tags"]


def test_kinds_add_up_and_a_span_finished_elsewhere_says_null():
    tr = trace.Tracer()
    handed: list = []

    def body():
        with tr.span("two_kinds"):
            with trace.blocked("map"):
                time.sleep(0.02)
            with trace.blocked("device"):
                time.sleep(0.01)
        sp = tr.span("handed_over")
        handed.append(sp)
        t = threading.Thread(target=sp.finish)
        t.start()
        t.join(timeout=10)

    by_name = {s["name"]: s for s in _traced(tr, body)["spans"]}
    two = by_name["two_kinds"]
    kinds = two["tags"]["blocked"]
    assert set(kinds) == {"map", "device"}
    assert kinds["map"] >= 19 and kinds["device"] >= 9
    assert two["blocked_ms"] == pytest.approx(sum(kinds.values()), abs=0.002)
    got = by_name["handed_over"]
    assert got["cpu_ms"] is None and got["blocked_ms"] is None
    # an interval recorded for another thread carries what it is given
    assert trace.BLOCKED_KINDS == ("queue", "map", "device", "lock")


def test_outside_any_span_nothing_is_timed(monkeypatch):
    reads = []
    monkeypatch.setattr(trace, "_now", lambda: reads.append(1) or time.monotonic())
    with trace.blocked("queue") as b:
        pass
    assert reads == [] and b.t1 is None
    # and a process without a tracer opens no span at all
    with trace.NOP_TRACER.span("coalesce"):
        with trace.blocked("queue"):
            pass
    assert reads == []


def test_a_spans_start_is_its_monotonic_stamp_on_the_wall_clock():
    tr = trace.Tracer()
    before = time.time()
    sp = tr.span("x")
    after = time.time()
    # one process-wide offset, taken at import: a disciplined wall clock
    # may since have moved a little against the monotonic one
    assert before - 0.5 <= sp.start <= after + 0.5
    assert sp.start == trace.wall(sp.opened)
    off = trace._wall_offset
    try:
        # a /debug/profile session takes the offset again as it opens
        trace._wall_offset = off + 100.0
        trace.set_profiling(None)
        assert trace._wall_offset == off + 100.0
        trace.set_profiling(type("Anno", (), {}))
        assert abs(trace._wall_offset - off) < 0.5
    finally:
        trace.set_profiling(None)
        trace._wall_offset = off


# ---------------------------------------------------------------------------
# the residency pool's one lock
# ---------------------------------------------------------------------------


def test_a_contended_pool_acquire_is_blocked_time_and_an_open_one_reads_no_clock(
    monkeypatch,
):
    pool = PlanePool(budget_bytes=1 << 20)
    tr = trace.Tracer()
    reads = []
    monkeypatch.setattr(trace, "_now", lambda: reads.append(1) or time.monotonic())

    # uncontended: every entry point, and no read of the clock
    pool.touch_many([("k",), ("j",)])
    with pool.pinned(("k",)):
        pass
    assert reads == []
    assert pool.gauges() == {"pool.lockWaits": 0, "pool.lockWaitMs": 0.0}

    held = threading.Event()
    release = threading.Event()

    def holder():
        with pool._mu:
            held.set()
            release.wait(timeout=30)

    t = threading.Thread(target=holder)
    t.start()
    assert held.wait(timeout=30)
    threading.Timer(0.05, release.set).start()

    def body():
        with tr.span("topn.prep"):
            pool.touch_many([("k",)])

    rec = _traced(tr, body)
    t.join(timeout=30)
    prep = next(s for s in rec["spans"] if s["name"] == "topn.prep")
    assert prep["tags"]["blocked"].keys() == {"lock"}
    assert prep["blocked_ms"] >= 40
    g = pool.gauges()
    assert g["pool.lockWaits"] == 1
    assert g["pool.lockWaitMs"] == pytest.approx(prep["blocked_ms"], abs=0.002)
    # a thread that holds the lock takes it again without a wait
    n = len(reads)
    with pool._mu:
        pool.touch_many([("k",)])
    assert len(reads) == n and pool.gauges()["pool.lockWaits"] == 1
    # the per-fragment touch is not timed at all (1,908 a miss: see pool.py)
    pool.touch(("k",))
    assert len(reads) == n


# ---------------------------------------------------------------------------
# the dispatcher: its life adds up, and it says what it was doing
# ---------------------------------------------------------------------------


def test_the_dispatchers_idle_launch_and_host_time_are_its_life():
    co = CoalesceScheduler(max_wait_us=0)
    try:
        b = jnp.asarray(np.ones((2, 1, 16), dtype=np.uint32))
        for i in range(5):
            time.sleep(0.02)
            assert int(co.submit(("leaf", 0), "count", b).result(timeout=60)[0][0]) == 16
        alive = co.snapshot()["dispatcher"]
        # a wait in progress counts up to the reading
        assert alive["idle_ms"] >= 80
        assert set(co.gauges()) == {
            "exec.dispatcher.idleMs", "exec.dispatcher.launchMs",
            "exec.dispatcher.hostMs", "exec.dispatcher.cycles"}
    finally:
        co.close()
    d = co.snapshot()["dispatcher"]
    assert d["cycles"] == co.snapshot()["launches"] == 5
    assert d["launch_ms"] > 0 and d["host_ms"] > 0
    parts = d["idle_ms"] + d["launch_ms"] + d["host_ms"]
    assert parts == pytest.approx(d["life_ms"], rel=0.01)


class _Held:
    """A fetch the test holds: ``jax.device_get`` asks it for an array."""

    def __init__(self):
        self.entered = threading.Event()
        self.release = threading.Event()

    def __array__(self, dtype=None, copy=None):
        self.entered.set()
        self.release.wait(timeout=30)
        return np.zeros(1, dtype=np.int32)


def _fetch_waiter(tr, co, arrays, out):
    def body():
        with tr.span("topn.fetch"):
            await_result(co.submit_fetch(arrays), 60)

    out.append(_traced(tr, body))


def test_a_lone_request_finds_the_dispatcher_idle_and_one_behind_a_launch_busy():
    tr = trace.Tracer()
    co = CoalesceScheduler(max_wait_us=0)
    try:
        time.sleep(0.05)  # the dispatcher is in its wait
        held = _Held()
        first: list = []
        second: list = []
        t1 = threading.Thread(target=_fetch_waiter, args=(tr, co, [held], first))
        t1.start()
        assert held.entered.wait(timeout=30)  # the launch is in flight
        t2 = threading.Thread(
            target=_fetch_waiter, args=(tr, co, [np.zeros(1)], second))
        t2.start()
        time.sleep(0.05)
        held.release.set()
        t1.join(timeout=30)
        t2.join(timeout=30)
    finally:
        co.close()
    (a,), (b,) = first, second
    for rec, state in ((a, "idle"), (b, "busy")):
        by_name = {s["name"]: s for s in rec["spans"]}
        assert sorted(by_name) == sorted(
            ["query", "topn.fetch", "launch", "handoff.queue", "handoff.wake"])
        launch = by_name["launch"]
        assert launch["parent_id"] == by_name["topn.fetch"]["span_id"]
        assert launch["tags"]["site"] == "fetch"
        q, w = by_name["handoff.queue"], by_name["handoff.wake"]
        assert q["parent_id"] == w["parent_id"] == launch["span_id"]
        assert q["tags"] == {"dispatcher": state}
        assert w["tags"] == {"waiters": 1}
        assert q["cpu_ms"] is None and w["blocked_ms"] is None
        # the three lie end to end on one clock
        assert q["start"] + q["duration_ms"] / 1e3 == pytest.approx(
            launch["start"], abs=1e-4)
        assert w["start"] >= launch["start"] + launch["duration_ms"] / 1e3 - 1e-4
    # the second waited out the launch in flight: that is its queue
    assert {s["name"]: s for s in b["spans"]}["handoff.queue"]["duration_ms"] >= 40


# ---------------------------------------------------------------------------
# a served Count: what the remainder means
# ---------------------------------------------------------------------------


COUNT = 'Count(Intersect(Bitmap(frame="f", rowID=1), Bitmap(frame="f", rowID=2)))'


@pytest.fixture
def server(tmp_path):
    s = Server(data_dir=str(tmp_path / "data"),
               stats=stats_mod.ExpvarStatsClient(), **QUIET)
    s.open()
    s.holder.create_index_if_not_exists("i")
    f = s.holder.index("i").create_frame_if_not_exists("f")
    for sl in range(2):
        for r in (1, 2):
            f.set_bit("standard", r, sl * SLICE_WIDTH + 5)
        f.set_bit("standard", 7, (sl + 1) * SLICE_WIDTH - 9)
    yield s
    s.close()


def _traces(c, n):
    _status, data = c._request("GET", "/debug/traces")
    return json.loads(data)["traces"][-n:]


def _share(traces, part, spans=("map.local",)):
    return metrics.load_reducer("span_time_share")(
        {"traces": traces}, spans=list(spans), part=part)


def _span_tag(t, name, tag):
    return next(s for s in t["spans"] if s["name"] == name)["tags"].get(tag)


def _spin(stop):
    x = 0
    while not stop.is_set():
        x += 1


def test_gil_wait_is_small_alone_and_large_beside_eight_spinning_threads(
    one_chip, server
):
    """One process compares two readings of itself.  Alone, a request's
    ``map.local`` is its own work and the wait for the dispatcher;
    beside eight threads of pure Python the same requests stand in line
    for the GIL, and both the remainder and ``handoff.wake`` say so.

    Who gets the GIL next is a lottery: a reading of a dozen requests
    can find the waits on the dispatcher's side instead (in
    ``handoff.queue`` with the dispatcher idle, and inside ``launch``),
    so the crowded reading is taken up to four times.  (On a mesh the
    launch runs on the health watchdog's thread and the waits land
    there as a rule: hence ``one_chip``.)"""
    c = InternalClient(server.host, timeout=120.0)
    # Every text a miss of the batch cache (the cache holds 8): a miss
    # plans, sweeps and dispatches on the request's own thread, so the
    # thread needs the GIL back a dozen times a request, where a cached
    # Count needs it once and leaves the rest to the dispatcher.
    texts = [COUNT.replace("Intersect", op).replace("rowID=1", f"rowID={a}")
             .replace("rowID=2", f"rowID={b}")
             for op in ("Intersect", "Union", "Difference", "Xor")
             for a, b in ((1, 2), (1, 7), (2, 7))]
    n = len(texts)
    for text in texts:  # compile every operator's programs
        c.execute_pql("i", text)

    def reading():
        for text in texts:
            c.execute_pql("i", text)
        traces = _traces(c, n)
        assert all(_span_tag(t, "plan", "batch_cache") == "miss" for t in traces)
        wake = statistics.median(
            s["duration_ms"] for t in traces for s in t["spans"]
            if s["name"] == "handoff.wake")
        parts = [_share(traces, p) for p in ("run", "blocked", "gil_wait")]
        assert sum(parts) == pytest.approx(100.0, abs=1e-6)
        return parts[2], wake, traces

    # (the calmer of two readings: six test workers share this machine)
    alone, wake_alone, traces = min(reading(), reading(), key=lambda r: r[0])
    # on one node the mapper runs on the request's own thread (a pool hop
    # would cost a context switch a query): nothing here waits for mappers
    for t in traces:
        by_name = {s["name"]: s for s in t["spans"]}
        assert "map" not in by_name["execute"]["tags"].get("blocked", {})
        assert by_name["map.local"]["tags"]["blocked"].keys() == {"queue"}
        # what execute stood blocked for is what its map.local did
        assert by_name["execute"]["blocked_ms"] == by_name["map.local"]["blocked_ms"]

    # six test workers share this machine, so "alone" is not idle (read
    # alone it is 2-4 %): the stated small share is under 40 % of a
    # map.local's wall time
    assert alone < 40.0, alone
    stop = threading.Event()
    spinners = [threading.Thread(target=_spin, args=(stop,), daemon=True)
                for _ in range(8)]
    for t in spinners:
        t.start()
    seen = []
    try:
        for _ in range(4):
            crowded, wake_crowded, _ = reading()
            seen.append((crowded, wake_crowded))
            # a waiter whose result is ready needs the GIL from eight
            # threads that hand it round every 5 ms
            if (crowded >= alone + 30.0
                    and wake_crowded > max(2.0, 3 * wake_alone)):
                break
        else:
            pytest.fail(f"alone {alone:.1f} % (wake {wake_alone:.3f} ms), "
                        f"crowded {seen}")
    finally:
        stop.set()
        for t in spinners:
            t.join(timeout=30)
    print(f"gil wait over map.local: alone {alone:.1f} % (wake {wake_alone:.3f} ms), "
          f"crowded {seen}")


def test_the_new_reducers_read_a_real_trace_and_metrics(server):
    c = InternalClient(server.host, timeout=120.0)

    def scrape():
        _status, data = c._request("GET", "/metrics")
        out = {}
        for line in data.decode().splitlines():
            if line and not line.startswith("#"):
                series, _, value = line.rpartition(" ")
                out[series] = float(value)
        return out

    assert c.execute_pql("i", COUNT) == 2
    t0 = time.monotonic()
    before = scrape()
    for _ in range(4):
        assert c.execute_pql("i", COUNT) == 2
    ev = {"traces": _traces(c, 4), "metrics": {"before": before, "after": scrape()},
          "window": (t0, time.monotonic())}
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    # the ten the host's account added, after the 47 that stood before it
    new = [m["name"] for m in bench["per_layer"][47:57]]
    assert len(new) == 10 and new[0] == "exec.handoff_queue_ms"
    got = {name: metrics.layer_metric(name, ev) for name in new}
    # a cached Count has no plan.leaves, and nothing here is a TopN or a Sum
    nothing = {"exec.plan_leaves_gil_wait_share", "exec.topn_prep_gil_wait_share",
               "exec.bsi_prep_gil_wait_share"}
    assert {k for k, v in got.items() if v is None} == nothing
    assert got["exec.handoff_queue_ms"] >= 0 and got["exec.handoff_wake_ms"] >= 0
    assert 0 < got["exec.run_share"] <= 100.5
    assert got["exec.run_share"] + got["exec.gil_wait_share"] <= 100.0 + 1e-6
    assert got["exec.dispatcher_launch_share"] > 0
    assert got["exec.dispatcher_host_share"] > 0
    assert (got["exec.dispatcher_launch_share"]
            + got["exec.dispatcher_host_share"]) <= 100.0 + 1e-6
    assert got["exec.pool_lock_wait_ms"] >= 0
    # four launches in the window, as the dispatcher counts its cycles
    cycles = "pilosa_exec_dispatcher_cycles"
    assert ev["metrics"]["after"][cycles] - before[cycles] == 4


def test_debug_stacks_gives_each_threads_cpu_seconds(server):
    stop = threading.Event()
    t = threading.Thread(target=_spin, args=(stop,), name="spins-unseen")
    t.start()
    try:
        c = InternalClient(server.host, timeout=60.0)

        def cpu_of(name):
            _status, data = c._request("GET", "/debug/stacks")
            line = next(ln for ln in data.decode().splitlines()
                        if ln.startswith(f"thread {name} "))
            return float(line.rsplit("cpu=", 1)[1].rstrip("s"))

        first = cpu_of("spins-unseen")
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and cpu_of("spins-unseen") < first + 0.05:
            time.sleep(0.05)
        # a thread that opens no span and holds the GIL shows between two calls
        assert cpu_of("spins-unseen") >= first + 0.05
        assert cpu_of("exec-coalesce") >= 0.0
    finally:
        stop.set()
        t.join(timeout=30)


# ---------------------------------------------------------------------------
# two nodes: the request thread waits for its own mappers
# ---------------------------------------------------------------------------


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_on_two_nodes_the_request_thread_is_blocked_for_its_mappers(tmp_path):
    ports: set = set()
    while len(ports) < 2:
        ports.add(_free_port())
    hosts = sorted(f"127.0.0.1:{p}" for p in ports)
    servers = []
    try:
        for i, host in enumerate(hosts):
            cluster = Cluster(replica_n=1)
            s = Server(data_dir=str(tmp_path / f"n{i}"), host=host,
                       cluster=cluster, **QUIET)
            s.open()
            servers.append(s)
            for h in hosts:
                if cluster.node_by_host(h) is None:
                    cluster.add_node(h)
            cluster.nodes.sort(key=lambda n: n.host)
        s0 = servers[0]
        for s in servers:
            s.holder.create_index_if_not_exists("i")
            s.holder.index("i").create_frame_if_not_exists("f")
        n_slices = 6
        for sl in range(n_slices):
            owner = s0.cluster.fragment_nodes("i", sl)[0].host
            srv = next(s for s in servers if s.host == owner)
            for r in (1, 2):
                srv.holder.frame("i", "f").set_bit("standard", r, sl * SLICE_WIDTH + 5)
        for s in servers:
            s.holder.index("i").set_remote_max_slice(n_slices - 1)
        owners = {s0.cluster.fragment_nodes("i", sl)[0].host for sl in range(n_slices)}
        assert owners == set(hosts)  # both nodes map

        c = InternalClient(s0.host, timeout=120.0)
        assert c.execute_pql("i", COUNT) == n_slices
        assert c.execute_pql("i", COUNT) == n_slices
        (t,) = _traces(c, 1)
        # (the other node's spans came home in the reply: this node's are
        # the root's ``execute`` and what lies beneath it)
        execute = next(s for s in t["spans"] if s["name"] == "execute"
                       and s["parent_id"] == t["spans"][0]["span_id"])
        call = next(s for s in t["spans"] if s["name"] == "call.Count"
                    and s["parent_id"] == execute["span_id"])
        # the mappers run on the pool's threads and on the other node:
        # the thread that opened call.Count waits for them, on purpose
        assert call["tags"]["blocked"].keys() == {"map"}
        assert call["blocked_ms"] >= 0.7 * call["duration_ms"]
        assert execute["tags"]["blocked"] == call["tags"]["blocked"]
        # its own mapper's wait for the dispatcher is the pool thread's
        local = next(s for s in t["spans"] if s["name"] == "map.local"
                     and s["tags"]["node"] == s0.host)
        assert local["parent_id"] == call["span_id"]
        assert local["tags"]["blocked"].keys() == {"queue"}
    finally:
        for s in servers:
            s.close()


# ---------------------------------------------------------------------------
# the accepted metrics read what they read
# ---------------------------------------------------------------------------


def _without_the_new(traces):
    """The traces as the parent program would have recorded them: no
    hand-over span, no ``blocked_ms``, no ``blocked`` tag, no ``cpu_ms``
    on a span recorded for another thread."""
    out = []
    for t in traces:
        spans = []
        for s in t["spans"]:
            if s["name"].startswith("handoff."):
                continue
            s = {k: v for k, v in s.items() if k != "blocked_ms"}
            s["tags"] = {k: v for k, v in s["tags"].items() if k != "blocked"}
            if s["name"] in ("launch", "compile"):
                s["cpu_ms"] = None
            spans.append(s)
        out.append(dict(t, spans=spans))
    return out


def test_the_47_accepted_metrics_read_the_same_with_the_new_spans_and_fields(
    one_chip, server
):
    v = server.holder.index("i").create_frame_if_not_exists("v")
    v.set_options(range_enabled=True)
    v.create_field("q", 0, 7)
    for sl in range(2):
        v.import_value("q", [sl * SLICE_WIDTH + 5, sl * SLICE_WIDTH + 9], [3, 7])
    server.holder.warm_device_mirrors()
    c = InternalClient(server.host, timeout=120.0)
    texts = [
        COUNT, COUNT, COUNT.replace("Intersect", "Union"),
        'TopN(Bitmap(frame="f", rowID=1), frame="f", n=10)',
        'TopN(Bitmap(frame="f", rowID=2), frame="f", n=10)',
        'Sum(Intersect(Bitmap(frame="f", rowID=1), Range(frame="v", q >< [2, 6])),'
        ' frame="v", field="q")',
    ]
    for text in texts:
        c.execute_pql("i", text)
    traces = _traces(c, len(texts))
    names = {s["name"] for t in traces for s in t["spans"]}
    assert {"handoff.queue", "handoff.wake", "coalesce", "topn.fetch",
            "bsi.fetch", "plan.leaves", "map.local"} <= names

    with open(os.path.join(BENCH, "tests", "fixtures", "evidence.json")) as f:
        ev = json.load(f)
    w0 = ev["window"][0]
    ev["traces"] = traces
    ev["records"] = [
        {"trace_id": t["trace_id"], "ok": True, "correct": True, "kind": "read",
         "text": text, "sent": w0 + i * 0.1, "done": w0 + i * 0.1 + 0.05,
         "latency_ms": t["duration_ms"] + 1.0}
        for i, (t, text) in enumerate(zip(traces, texts))]
    old = dict(ev, traces=_without_the_new(traces))
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    accepted = [m["name"] for m in bench["per_layer"][:47]]
    assert "device.tanimoto_handback_one_share" == accepted[-1]
    read = 0
    for name in accepted:
        with_new = metrics.layer_metric(name, ev)
        assert with_new == metrics.layer_metric(name, old), name
        read += with_new is not None
    # the span medians and shares of every cell found something to read
    # (the TopN and BSI rooflines read a profile of their own cells)
    assert read >= 40, read
    for name in ("exec.coalesce_wait_ms", "exec.map_local_self_ms",
                 "exec.bsi_map_local_self_ms", "device.launch_ms",
                 "device.topn_fetch_ms", "device.bsi_fetch_ms",
                 "exec.map_local_cpu_share", "device.window_compile_ms"):
        assert metrics.layer_metric(name, ev) is not None, name
