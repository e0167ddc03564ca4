"""The spans where the host's seconds go: what a span records (wall and
CPU time, spans recorded for another thread), the stages of a served
Count (the anchored pre-pass, the children of ``plan``, the dispatcher's
``launch`` and its ``compile``), and the profile ``/debug/profile``
takes of them."""

import glob
import json
import tarfile
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from pilosa_tpu.core import cache as cache_mod
from pilosa_tpu.exec import plan
from pilosa_tpu.exec.coalesce import CoalesceScheduler
from pilosa_tpu.net import handler as handler_mod
from pilosa_tpu.net.client import InternalClient
from pilosa_tpu.net.server import Server
from pilosa_tpu.obs import stats as stats_mod
from pilosa_tpu.obs import trace
from pilosa_tpu.ops import bitplane as bp
from pilosa_tpu.ops.bitplane import SLICE_WIDTH

PLAN_STAGES = ("plan.resolve", "plan.leaves", "plan.transfer", "plan.register")


# ---------------------------------------------------------------------------
# what a span records
# ---------------------------------------------------------------------------


def _one_span(tr, body):
    root = tr.start_trace("query")
    token = root.activate()
    with tr.span("work"):
        body()
    root.deactivate(token)
    rec = tr.finish_root(root)
    return next(s for s in rec["spans"] if s["name"] == "work"), rec


def _spin():
    # 50 ms of this thread's own CPU time, however long the workers
    # beside it make that take on the wall clock
    end = time.thread_time() + 0.05
    while time.thread_time() < end:
        pass


@pytest.mark.parametrize("body,busy", [(_spin, True),
                                       (lambda: time.sleep(0.05), False)])
def test_cpu_ms_is_the_threads_time_on_the_processor(body, busy):
    span, rec = _one_span(trace.Tracer(), body)
    assert span["duration_ms"] >= 45
    assert span["cpu_ms"] is not None
    assert span["cpu_ms"] <= span["duration_ms"] + 1
    if busy:
        assert span["cpu_ms"] >= 45
    else:
        assert span["cpu_ms"] < 10
    # the root carries it too, and the export header with it
    assert rec["spans"][0]["cpu_ms"] is not None
    assert '"cpu_ms"' in trace.Tracer.export_payload(rec)


def test_a_span_finished_on_another_thread_has_no_cpu_ms():
    tr = trace.Tracer()
    root = tr.start_trace("query")
    sp = tr.span("handed_over", parent=root)
    t = threading.Thread(target=sp.finish)
    t.start()
    t.join(timeout=10)
    rec = tr.finish_root(root)
    got = next(s for s in rec["spans"] if s["name"] == "handed_over")
    assert got["duration_ms"] is not None and got["cpu_ms"] is None


def test_add_span_lands_under_its_parent_and_is_dropped_once_final():
    tr = trace.Tracer()
    root = tr.start_trace("query")
    parent = tr.span("coalesce", parent=root)
    child = tr.add_span(parent, "launch", 1234.5, 7.25, site="total")
    tr.add_span(child, "compile", 1234.6, 3.0, family="plan.batched")
    parent.finish()
    rec = tr.finish_root(root)
    by_name = {s["name"]: s for s in rec["spans"]}
    launch = by_name["launch"]
    assert launch["parent_id"] == parent.span_id
    assert (launch["start"], launch["duration_ms"]) == (1234.5, 7.25)
    assert launch["cpu_ms"] is None and launch["tags"] == {"site": "total"}
    assert by_name["compile"]["parent_id"] == launch["span_id"]
    # the trace is final: a launch that ends now is not recorded
    tr.add_span(parent, "launch", 1.0, 1.0)
    assert [s["name"] for s in tr.traces()[-1]["spans"]].count("launch") == 1
    # tracing disabled: a no-op that still hands back a parent
    nop = trace.NOP_TRACER.add_span(trace.NOP_SPAN, "launch", 1.0, 1.0)
    assert nop.add_child("compile", 1.0, 1.0) is nop
    assert not hasattr(trace.Tracer(), "late_spans")


# ---------------------------------------------------------------------------
# the dispatcher's launch, once per waiter
# ---------------------------------------------------------------------------


def _waiter(tr, co, expr, batch, out):
    root = tr.start_trace("query")
    token = root.activate()
    with tr.span("coalesce"):
        co.submit(expr, "count", batch).result(timeout=60)
    root.deactivate(token)
    out.append(tr.finish_root(root))


def test_two_queries_on_one_launch_each_get_the_same_launch_span(rng):
    tr = trace.Tracer()
    co = CoalesceScheduler(max_wait_us=300_000)
    try:
        # a shape no other test has called, so this launch is a first call
        batch = jnp.asarray(
            rng.integers(0, 2**32, size=(4, 2, 48), dtype=np.uint32))
        expr = ("Xor", ("leaf", 0), ("leaf", 1))
        recs: list = []
        threads = [threading.Thread(target=_waiter,
                                    args=(tr, co, expr, batch, recs))
                   for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert len(recs) == 2
        launches = []
        for rec in recs:
            by_name = {s["name"]: s for s in rec["spans"]}
            launch = by_name["launch"]
            assert launch["parent_id"] == by_name["coalesce"]["span_id"]
            assert launch["tags"]["site"] == "coalesce"
            assert launch["tags"]["queries"] == 2
            assert launch["tags"]["first_call"] is True
            assert launch["tags"]["dispatch_ms"] <= launch["duration_ms"]
            # the dispatcher opened and closed it on one thread: its CPU
            # time and its blocked time are carried into every copy
            assert 0 <= launch["cpu_ms"] <= launch["duration_ms"] + 1
            assert launch["blocked_ms"] is not None
            # the launch lies inside the wait for it
            assert launch["duration_ms"] <= by_name["coalesce"]["duration_ms"]
            compile_ = by_name["compile"]
            assert compile_["parent_id"] == launch["span_id"]
            assert compile_["tags"]["family"] == "plan.batched"
            assert "(4, 2, 48)" in compile_["tags"]["shape"]
            launches.append(launch)
        assert launches[0]["start"] == launches[1]["start"]
        assert launches[0]["duration_ms"] == launches[1]["duration_ms"]
        assert launches[0]["span_id"] != launches[1]["span_id"]
        # the same shape again: a launch, and no compile
        again: list = []
        _waiter(tr, co, expr, batch, again)
        names = [s["name"] for s in again[0]["spans"]]
        assert names.count("launch") == 1 and "compile" not in names
        assert next(s for s in again[0]["spans"]
                    if s["name"] == "launch")["tags"]["first_call"] is False
    finally:
        co.close()


def test_a_direct_first_call_compiles_under_the_current_span(rng):
    tr = trace.Tracer()
    batch = jnp.asarray(rng.integers(0, 2**32, size=(2, 2, 80), dtype=np.uint32))
    prog = plan.compiled_batched(("Union", ("leaf", 0), ("leaf", 1)), "count")
    root = tr.start_trace("query")
    token = root.activate()
    with tr.span("exec.device") as dev:
        prog(batch)
        prog(batch)
    root.deactivate(token)
    rec = tr.finish_root(root)
    compiles = [s for s in rec["spans"] if s["name"] == "compile"]
    assert len(compiles) == 1 and compiles[0]["parent_id"] == dev.span_id
    # outside any trace the first call records nothing and does not raise
    plan.compiled_batched(("Xor", ("leaf", 0), ("leaf", 1)), "count")(batch)


# ---------------------------------------------------------------------------
# a served Count
# ---------------------------------------------------------------------------


@pytest.fixture
def server(tmp_path):
    s = Server(
        data_dir=str(tmp_path / "data"),
        stats=stats_mod.ExpvarStatsClient(),
        anti_entropy_interval=3600,
        polling_interval=3600,
        cache_flush_interval=3600,
    )
    s.open()
    yield s
    s.close()


def _populate(s, slices):
    s.holder.create_index_if_not_exists("i")
    f = s.holder.index("i").create_frame_if_not_exists("f")
    for sl in range(slices):
        for r in (1, 2):
            f.set_bit("standard", r, sl * SLICE_WIDTH + 5)
        f.set_bit("standard", 1, sl * SLICE_WIDTH + 9)
        # a column at the slice's end, in a row no text asks about: planes
        # of full width, the shapes of a filled index (narrow ones are
        # tests/test_narrow_planes.py's)
        f.set_bit("standard", 7, (sl + 1) * SLICE_WIDTH - 9)


def _stage_mirrors(s):
    """The plane mirrors resident, as on a node that has served: a miss
    then gathers its rows on the device."""
    for frag in s.holder.view("i", "f", "standard").fragments():
        frag.device_plane()


def _last_trace(c):
    _status, data = c._request("GET", "/debug/traces")
    return json.loads(data)["traces"][-1]


def _span(t, name):
    return next(s for s in t["spans"] if s["name"] == name)


def _children(t, name):
    parent = next(s for s in t["spans"] if s["name"] == name)
    return [s for s in t["spans"] if s["parent_id"] == parent["span_id"]]


def _tags(span):
    """A span's tags less ``blocked``, which says how long its thread
    stood waiting on purpose and is no tag of the work."""
    return {k: v for k, v in span["tags"].items() if k != "blocked"}


HANDOFFS = ["handoff.queue", "handoff.wake"]


INTERSECT = 'Count(Intersect(Bitmap(frame="f", rowID=1), Bitmap(frame="f", rowID=2)))'
UNION = 'Count(Union(Bitmap(frame="f", rowID=1), Bitmap(frame="f", rowID=2)))'


def test_the_stages_of_a_served_count(server):
    _populate(server, 3)
    _stage_mirrors(server)
    c = InternalClient(server.host, timeout=60.0)
    assert c.execute_pql("i", INTERSECT) == 3
    miss = _last_trace(c)
    pre = [s for s in miss["spans"] if s["name"] == "anchored.prepass"]
    assert len(pre) == 1
    # the test corpus is dense-tier: the view holds no sparse-tier row, so
    # the metadata pass declined before walking a slice, and no anchor's
    # positions were read (anchors_scanned counts row_positions calls:
    # 0 on every decline; both read 3 while the scan came before the test)
    assert pre[0]["tags"] == {"outcome": "declined_dense", "slices_walked": 0,
                              "anchors_scanned": 0}
    assert pre[0] in _children(miss, "map.local")
    plan_span = next(s for s in miss["spans"] if s["name"] == "plan")
    assert plan_span["tags"]["batch_cache"] == "miss"
    stages = _children(miss, "plan")
    assert [s["name"] for s in stages] == list(PLAN_STAGES)
    assert sum(s["duration_ms"] for s in stages) <= plan_span["duration_ms"]
    by_name = {s["name"]: s for s in stages}
    assert by_name["plan.resolve"]["tags"]["fragments"] == 6
    leaves = by_name["plan.leaves"]["tags"]
    assert leaves["path"] == "plane_gather"
    assert leaves["rows"] == 6
    assert leaves["device_copies"] == leaves["launches"] > 0
    assert by_name["plan.transfer"]["tags"]["bytes"] > 0
    assert by_name["plan.transfer"]["tags"]["devices"] >= 1
    assert by_name["plan.register"]["tags"]["displaced"] == 0
    launch = [s for s in miss["spans"] if s["name"] == "launch"]
    assert len(launch) == 1 and launch[0] in _children(miss, "coalesce")
    # the tags that guessed at compiles are gone; the compile is a span
    for s in miss["spans"]:
        assert "warm" not in s["tags"] and "persistent_cache" not in s["tags"]

    assert c.execute_pql("i", INTERSECT) == 3
    hit = _last_trace(c)
    assert next(s for s in hit["spans"]
                if s["name"] == "plan")["tags"]["batch_cache"] == "hit"
    assert [s["name"] for s in _children(hit, "plan")] == ["plan.resolve"]
    assert "compile" not in {s["name"] for s in hit["spans"]}

    # a Union has no leaf that bounds it: the pre-pass ends before its loop
    assert c.execute_pql("i", UNION) == 6
    pre = next(s for s in _last_trace(c)["spans"]
               if s["name"] == "anchored.prepass")
    assert pre["tags"] == {"outcome": "not_eligible", "slices_walked": 0,
                           "anchors_scanned": 0}


def test_a_miss_over_cold_planes_fills_on_the_host(server, monkeypatch):
    _populate(server, 3)
    # (the prefetcher uploads a cold leaf's mirror as the query starts; held
    # back here, or on a slow day half of them are up before the sweep)
    monkeypatch.setattr(server.executor.prefetcher, "prefetch", lambda frags: 0)
    c = InternalClient(server.host, timeout=60.0)
    assert c.execute_pql("i", INTERSECT) == 3
    stages = {s["name"]: s for s in _children(_last_trace(c), "plan")}
    assert tuple(stages) == PLAN_STAGES
    # the mirrors are on their way up (the prefetcher's doing, here held
    # back): the next miss will gather, and its programs compile beside
    # this fill, on a thread no request waits for
    server.executor._gather_warming.join(timeout=120)
    assert plan.program_cache_stats()["bitplane.gatherPlanes"] > 0
    assert stages["plan.resolve"]["tags"] == {"fragments": 6, "cold": 6}
    leaves = stages["plan.leaves"]["tags"]
    assert leaves["path"] in ("host_fill", "mesh_host_fill")
    assert leaves["rows"] == 6 and leaves["device_copies"] == 0


MISS_SPANS = sorted([
    "query", "parse", "admission", "execute", "call.Count", "map.local",
    "anchored.prepass", "plan", *PLAN_STAGES, "coalesce", "launch",
    "handoff.queue", "handoff.wake"])


@pytest.mark.parametrize("slices", [2, 70])
def test_the_gather_of_a_miss_has_the_same_spans_at_any_slice_count(
    one_chip, server, slices
):
    """``plan.leaves`` (the slot sweep, the ceil(slices / 64) launches of
    the gather and their writes into the block) and ``plan.transfer``
    (what is left of the assembly), a ``compile`` for a program shape's
    first call, and as many spans at 70 slices as at 2."""
    _populate(server, slices)
    _stage_mirrors(server)
    c = InternalClient(server.host, timeout=120.0)
    assert c.execute_pql("i", UNION) == 2 * slices  # the count program
    plan.clear_program_caches()
    entries = plan.program_cache_stats()["bitplane.gatherPlanes"]
    assert entries == 0
    assert c.execute_pql("i", INTERSECT) == slices
    first = _last_trace(c)
    launches = -(-slices // bp.SCORE_GROUP)
    leaves = _span(first, "plan.leaves")
    assert leaves["tags"] == {"path": "plane_gather", "rows": 2 * slices,
                              "launches": launches, "device_copies": launches}
    transfer = _span(first, "plan.transfer")["tags"]
    assert transfer["devices"] == 1 and transfer["spilled"] == 0
    assert transfer["bytes"] == plan.slice_bucket(slices) * 2 * bp.WORDS_PER_SLICE * 4
    # one gather program whatever the launches; where a launch does not
    # fill the block, one more writes it there
    compiled = [s for s in first["spans"] if s["name"] == "compile"
                and s["tags"]["family"] == "plan.gather"]
    assert [s["parent_id"] for s in compiled] == (
        [leaves["span_id"]] * (1 if launches == 1 else 2))
    stats, bounds = plan.program_cache_stats(), plan.program_cache_bounds()
    assert stats["bitplane.gatherPlanes"] == len(compiled)
    assert stats["bitplane.gatherPlanes"] <= bounds["bitplane.gatherPlanes"]
    # the gauge and each span round to a microsecond on their own
    assert plan.program_cache_compile_ms()["plan.gather"] >= sum(
        s["duration_ms"] for s in compiled) - 0.001 * len(compiled)

    # another operator over other rows: the programs that are there
    assert c.execute_pql("i", INTERSECT.replace("Intersect", "Xor")) == slices
    other = _last_trace(c)
    assert plan.program_cache_stats()["bitplane.gatherPlanes"] == len(compiled)
    assert sorted(s["name"] for s in other["spans"]
                  if s["name"] != "compile") == MISS_SPANS
    assert not [s for s in other["spans"] if s["name"] == "compile"
                and s["tags"]["family"] == "plan.gather"]


@pytest.mark.parametrize("slices", [2, 40])
def test_no_span_sits_in_a_loop_over_slices(server, slices):
    _populate(server, slices)
    c = InternalClient(server.host, timeout=60.0)
    assert c.execute_pql("i", INTERSECT) == slices
    # 24 until the two hand-overs of a launch became spans (PR 38)
    assert len(_last_trace(c)["spans"]) <= 26
    assert c.execute_pql("i", INTERSECT) == slices
    assert len(_last_trace(c)["spans"]) <= 26


TOPN_SRC = 'TopN(Bitmap(frame="f", rowID=1), frame="f", n=10)'



@pytest.mark.parametrize("slices", [2, 70])
def test_the_stages_of_a_served_topn_and_no_span_in_a_loop_over_slices(
    one_chip, server, slices, monkeypatch
):
    """``topn.prep`` / ``topn.score`` > ``topn.dispatch`` > ``compile``,
    ``topn.fetch`` / ``topn.select`` with the tags the per-layer metrics
    read, and as many spans at 70 slices (two launches of the scorer) as
    at 2."""
    _populate(server, slices)
    plan.clear_program_caches()
    # The memo lives from before the scorer's first call, and under six
    # test workers that compile has outlasted its 10 s.
    monkeypatch.setattr(cache_mod, "RECALCULATE_INTERVAL_S", 600.0)
    c = InternalClient(server.host, timeout=120.0)
    def topn(text):
        return [(p.id, p.count) for p in c.execute_pql("i", text)]

    want = [(1, 2 * slices), (2, slices)]
    assert topn(TOPN_SRC) == want
    first = _last_trace(c)
    assert len(first["spans"]) <= 26
    prep = _span(first, "topn.prep")
    # every fragment ranks the union itself and holds the src in its
    # plane: no fragment walked, no host copy of the src (``build``), and
    # the view's stack is kept for the next text (``stack``); the union
    # is rows 1, 2 and the row that holds the far column
    assert prep["tags"] == {"slices": slices, "prep_cache": "built", "union": 3,
                            "build": "direct", "stack": "made"}
    disp = _span(first, "topn.dispatch")
    launches = -(-slices // bp.SCORE_GROUP)
    assert disp["tags"]["launches"] == launches and disp["tags"]["groups"] == 1
    assert disp["tags"]["rows"] == slices * bp.ROW_BLOCK
    assert disp["tags"]["bytes"] == slices * bp.ROW_BLOCK * bp.WORDS_PER_SLICE * 4
    assert disp in _children(first, "topn.score")
    assert _span(first, "topn.score")["tags"]["score_cache"] == "computed"
    # the program shape's first call compiled under the dispatch, once
    # however many launches there were, and compileMs counted it
    compiles = _children(first, "topn.dispatch")
    assert [s["name"] for s in compiles] == ["compile"]
    assert compiles[0]["tags"]["family"] == "topn.score"
    assert plan.program_cache_compile_ms()["topn.score"] >= compiles[0]["duration_ms"]
    assert _span(first, "topn.fetch")["tags"]["arrays"] == launches
    # selected from the entry's stacked arrays, no call a part (``way``)
    assert _span(first, "topn.select")["tags"] == {"parts": slices, "way": "stacked"}

    # the same text again inside the memo's lifetime: nothing is scored
    assert topn(TOPN_SRC) == want
    again = _last_trace(c)
    assert _span(again, "topn.prep")["tags"]["prep_cache"] == "hit"
    assert "build" not in _span(again, "topn.prep")["tags"]  # nothing built
    assert _span(again, "topn.score")["tags"]["score_cache"] == "shared"
    assert not {"topn.dispatch", "compile"} & {s["name"] for s in again["spans"]}

    # another src: built from the kept stack, whose layout no text
    # changes, and scored by the program that is there
    assert topn(TOPN_SRC.replace("rowID=1", "rowID=2")) == [
        (1, slices), (2, slices)]
    other = _last_trace(c)
    assert _span(other, "topn.score")["tags"]["score_cache"] == "computed"
    assert _tags(_span(other, "topn.prep")) == {
        "slices": slices, "prep_cache": "built", "union": 3, "build": "direct",
        "stack": "kept"}
    assert "compile" not in {s["name"] for s in other["spans"]}
    # a TopN(src) that is scored has these 13 spans whatever the slice count
    assert sorted(s["name"] for s in other["spans"]) == sorted([
        "query", "parse", "admission", "execute", "call.TopN", "topn.prep",
        "topn.score", "topn.dispatch", "topn.fetch", "launch", *HANDOFFS,
        "topn.select"])


SUM_FILTERED = 'Sum(Intersect(Bitmap(frame="f", rowID=1), Range(frame="v", q >< [2, 6])), frame="v", field="q")'


@pytest.mark.parametrize("slices", [2, 20])
def test_the_stages_of_a_served_sum_and_no_span_in_a_loop_over_slices(
    one_chip, server, slices
):
    """``bsi.agg`` › ``bsi.prep``, ``bsi.dispatch`` › ``compile``,
    ``bsi.fetch`` › ``launch``, ``bsi.decode`` with the tags the
    per-layer metrics read, and as many spans at 20 slices (two
    launches of the aggregate's program) as at 2."""
    _populate(server, slices)
    v = server.holder.index("i").create_frame_if_not_exists("v")
    v.set_options(range_enabled=True)
    v.create_field("q", 0, 7)
    for sl in range(slices):
        v.import_value("q", [sl * SLICE_WIDTH + 5, sl * SLICE_WIDTH + 9], [3, 7])
    server.holder.warm_device_mirrors()  # cold ones take the leaf batch
    plan.clear_program_caches()
    c = InternalClient(server.host, timeout=120.0)
    def ask(text):
        # the internal client's protobuf leg carries a ValCount as one Pair
        return [(p.id, p.count) for p in c.execute_pql("i", text)]

    assert ask(SUM_FILTERED) == [(3 * slices, slices)]
    first = _last_trace(c)
    assert len(first["spans"]) <= 26
    agg = _span(first, "bsi.agg")
    launches = -(-slices // bp.agg_members(slices, bp.TILE_ROWS + 8 + 9))
    # exists + 3 magnitude rows of q read twice (the Sum's and the
    # Range's leaves) and the filter's row: 9 rows of planes a slice
    assert _tags(agg) == {"slices": slices, "way": "in_place", "planes": 9 * slices,
                          "bytes": 9 * slices * bp.WORDS_PER_SLICE * 4,
                          "launches": launches}
    assert [s["name"] for s in _children(first, "bsi.agg")] == [
        "bsi.prep", "bsi.dispatch", "bsi.fetch", "bsi.decode"]
    assert _span(first, "bsi.prep")["tags"] == {"slices": slices, "cold": 0}
    disp = _span(first, "bsi.dispatch")
    assert disp["tags"] == {"groups": 1, "launches": launches}
    # the program shape's first call compiled under the dispatch, once
    # however many launches there were, and compileMs counted it
    compiles = _children(first, "bsi.dispatch")
    assert [s["name"] for s in compiles] == ["compile"]
    assert compiles[0]["tags"]["family"] == "bsi.agg"
    assert plan.program_cache_compile_ms()["bsi.agg"] >= compiles[0]["duration_ms"]
    assert _span(first, "bsi.fetch")["tags"]["arrays"] == launches
    assert [s["name"] for s in _children(first, "bsi.fetch")] == ["launch"]
    assert [s["name"] for s in _children(first, "launch")] == HANDOFFS
    assert _span(first, "bsi.decode")["tags"] == {"vectors": slices}

    # other constants and another row: the program that is there
    assert ask(SUM_FILTERED.replace("[2, 6]", "[7, 7]").replace(
        "rowID=1", "rowID=2")) == [(0, 0)]
    assert ask(SUM_FILTERED.replace("[2, 6]", "[4, 7]")) == [(7 * slices, slices)]
    other = _last_trace(c)
    assert plan.program_cache_stats()["bitplane.aggregatePlanes"] == 1
    # an in-place Sum has these 14 spans whatever the slice count
    assert sorted(s["name"] for s in other["spans"]) == sorted([
        "query", "parse", "admission", "execute", "call.Sum", "map.local",
        "bsi.agg", "bsi.prep", "bsi.dispatch", "bsi.fetch", "launch", *HANDOFFS,
        "bsi.decode"])


# ---------------------------------------------------------------------------
# the hand-overs to and from the dispatcher
# ---------------------------------------------------------------------------


def _served_counts(server, c):
    _populate(server, 3)
    return [INTERSECT] * 6, "coalesce", "coalesce"


def _served_topns(server, c):
    _populate(server, 3)
    # a text each, or the score memo answers and nothing is fetched
    return [TOPN_SRC.replace("n=10", f"n={n}") for n in (10, 9, 8, 7, 6, 5)], \
        "topn.fetch", "fetch"


def _served_sums(server, c):
    _populate(server, 3)
    v = server.holder.index("i").create_frame_if_not_exists("v")
    v.set_options(range_enabled=True)
    v.create_field("q", 0, 7)
    for sl in range(3):
        v.import_value("q", [sl * SLICE_WIDTH + 5, sl * SLICE_WIDTH + 9], [3, 7])
    server.holder.warm_device_mirrors()  # cold ones take the leaf batch
    return [SUM_FILTERED.replace("[2, 6]", f"[{lo}, 6]") for lo in range(6)], \
        "bsi.fetch", "fetch"


@pytest.mark.parametrize("served", [_served_counts, _served_topns, _served_sums])
def test_a_waiters_launch_is_flanked_by_its_two_handovers(one_chip, server, served):
    """Every launch a request waited on carries exactly one
    ``handoff.queue`` and one ``handoff.wake``, children of the waiter's
    copy of the ``launch``; the three lie end to end and cover the
    waiter's ``result()`` (its blocked time of kind ``queue``) to 1 ms;
    a lone request finds the dispatcher idle."""
    c = InternalClient(server.host, timeout=120.0)
    texts, waits_in, site = served(server, c)
    c.execute_pql("i", texts.pop())  # compile
    left_over = []
    found = []
    for text in texts:
        c.execute_pql("i", text)
        t = _last_trace(c)
        launches = [s for s in t["spans"] if s["name"] == "launch"]
        assert len(launches) == 1 and launches[0]["tags"]["site"] == site
        launch = launches[0]
        waiter = _span(t, waits_in)
        assert launch["parent_id"] == waiter["span_id"]
        kids = _children(t, "launch")
        assert [s["name"] for s in kids] == HANDOFFS
        assert len([s for s in t["spans"] if s["name"] in HANDOFFS]) == 2
        queue, wake = kids
        assert queue["tags"].keys() == {"dispatcher"}
        found.append(queue["tags"]["dispatcher"])
        assert wake["tags"] == {"waiters": 1}
        assert queue["duration_ms"] >= 0 and wake["duration_ms"] >= 0
        # the launch carries the dispatcher's own CPU time
        assert launch["cpu_ms"] is not None
        waited = waiter["tags"]["blocked"]["queue"]
        left_over.append(waited - (queue["duration_ms"] + launch["duration_ms"]
                                   + wake["duration_ms"]))
        # the wait is inside the span that made it
        assert waited <= waiter["duration_ms"] + 0.01
    # between the launch's end and set_result the dispatcher publishes the
    # span and counts the launch: the median request's is under 1 ms
    # a lone request finds the dispatcher in its wait (on a crowded
    # machine the dispatcher can lose the processor for 5 ms on its way
    # there, and the next request then finds it busy: tests/
    # test_host_account.py holds both states to a launch the test holds)
    assert found.count("idle") >= len(found) - 2, found
    left_over.sort()
    assert abs(left_over[len(left_over) // 2]) < 1.0, left_over


# ---------------------------------------------------------------------------
# /debug/profile
# ---------------------------------------------------------------------------


class _FakeProfiler:
    """Stands in for ``jax.profiler``: keeps what ``trace`` was given."""

    class ProfileOptions:
        python_tracer_level = 1
        host_tracer_level = 2

    class TraceAnnotation:
        def __init__(self, name, **kw):
            pass

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    def __init__(self):
        self.sessions = []

    def trace(self, log_dir, profiler_options=None):
        fake = self

        class _Session:
            def __enter__(self):
                fake.sessions.append(profiler_options)

            def __exit__(self, *exc):
                with open(f"{log_dir}/fake.xplane.pb", "wb") as f:
                    f.write(b"x")
                return False

        return _Session()


@pytest.mark.parametrize("query,level", [("", 0), ("&python=1", 1),
                                         ("&python=0", 0)])
def test_profile_leaves_the_python_tracer_off_unless_asked(
        server, monkeypatch, query, level):
    fake = _FakeProfiler()
    monkeypatch.setattr(handler_mod, "_jax_profiler", lambda: fake)
    c = InternalClient(server.host, timeout=30.0)
    status, data, _ = c._request_meta("GET", f"/debug/profile?seconds=0.05{query}")
    assert status == 200 and json.loads(data)["bytes"] > 0
    (opts,) = fake.sessions
    assert opts.python_tracer_level == level
    assert opts.host_tracer_level == 2  # the host tracer stays as it is
    assert trace._annotation is None  # the session's flag is cleared


def test_a_profile_taken_while_a_query_runs_holds_the_programs_spans(
        server, tmp_path):
    from jax.profiler import ProfileData

    _populate(server, 3)
    c = InternalClient(server.host, timeout=60.0)
    # compile outside the profile: under six test workers a first call
    # has outlasted the 1 s window, and no span then opens inside it
    assert c.execute_pql("i", INTERSECT) == 3
    assert c.execute_pql("i", UNION) == 6
    reply: dict = {}

    def profile():
        status, data, _ = InternalClient(server.host, timeout=120.0)._request_meta(
            "GET", "/debug/profile?seconds=1")
        reply.update(status=status, doc=json.loads(data))

    t = threading.Thread(target=profile)
    t.start()
    deadline = time.monotonic() + 30
    while t.is_alive() and time.monotonic() < deadline:
        assert c.execute_pql("i", UNION) == 6
        assert c.execute_pql("i", INTERSECT) == 3
    t.join(timeout=120)
    assert not t.is_alive()
    if reply["status"] == 501:
        pytest.skip("this runtime has no profiler")
    assert reply["status"] == 200
    with tarfile.open(reply["doc"]["trace"]) as tf:
        tf.extractall(tmp_path / "prof", filter="data")
    (pb,) = glob.glob(str(tmp_path / "prof" / "**" / "*.xplane.pb"),
                      recursive=True)
    data = ProfileData.from_file(pb)
    planes = {p.name: p for p in data.planes}
    # what benchmarks/xplane.py needs of a profile without the Python tracer
    stats = dict(planes["Task Environment"].stats)
    assert stats["profile_stop_time"] > stats["profile_start_time"]
    names = {ev.name for n, p in planes.items() if n.startswith("/host:")
             for ln in p.lines for ev in ln.events}
    assert {"plan", "map.local", "anchored.prepass", "launch"} <= names
    assert trace._annotation is None
