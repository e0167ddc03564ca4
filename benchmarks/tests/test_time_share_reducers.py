"""The two reducers that read a span's account of its thread's time
(``span_time_share``) and the growth of several ``/metrics`` series
against each other (``counter_share``), on hand-made evidence: the
values are worked out in the comments.  A program that records no
``blocked_ms`` and publishes no such series (the parent of the PR that
added them) gives each nothing to read, and neither raises."""

import pytest

import metrics


def read(ev, reducer, **args):
    return metrics.load_reducer(reducer)(ev, **args)


def span(name, ms, cpu=None, blocked_ms=None, parent=None, sid=None, **tags):
    return {"name": name, "span_id": sid or name, "parent_id": parent, "start": 0.0,
            "duration_ms": ms, "cpu_ms": cpu, "blocked_ms": blocked_ms, "tags": tags}


@pytest.fixture()
def ev():
    return {"traces": [
        # a Count: 100 ms of map.local, 20 on the processor, 60 waiting for
        # the coalescer's future, of which the last 10 with the result ready
        {"spans": [
            span("map.local", 100.0, 20.0, 60.0, blocked={"queue": 60.0}),
            span("plan", 15.0, 10.0, 0.0, parent="map.local"),
            span("plan.leaves", 10.0, 2.0, 0.0, parent="plan"),
            span("coalesce", 62.0, 1.0, 60.0, parent="map.local",
                 blocked={"queue": 60.0}),
            span("launch", 30.0, 3.0, 0.0, parent="coalesce"),
            span("handoff.queue", 20.0, parent="launch", dispatcher="busy"),
            span("handoff.wake", 10.0, parent="launch", waiters=2),
        ]},
        # a TopN: 50 ms of call.TopN, 30 on the processor, 5 for a lock and
        # 5 for the fetch, 1 of it the wake; topn.prep under it is not
        # counted twice when both are listed
        {"spans": [
            span("call.TopN", 50.0, 30.0, 10.0, blocked={"queue": 5.0, "lock": 5.0}),
            span("topn.prep", 20.0, 12.0, 5.0, parent="call.TopN",
                 blocked={"lock": 5.0}),
            span("topn.fetch", 6.0, 0.5, 5.0, parent="call.TopN",
                 blocked={"queue": 5.0}),
            span("launch", 3.0, 0.2, 0.0, parent="topn.fetch", sid="launch2"),
            span("handoff.wake", 1.0, parent="launch2", sid="wake2", waiters=1),
        ]},
        # finished on another thread: no cpu_ms, no blocked_ms, left out
        {"spans": [span("map.local", 1000.0)]},
    ]}


WORK = ["map.local", "call.TopN"]


def test_the_three_parts_come_to_a_hundred(ev):
    # run: (20 + 30) / (100 + 50)
    assert read(ev, "span_time_share", spans=WORK, part="run") == pytest.approx(100 / 3)
    # blocked: (60 - 10 + 10 - 1) / 150: each wake is put back
    assert read(ev, "span_time_share", spans=WORK, part="blocked") == pytest.approx(
        100 * 59 / 150)
    # what is left: (100 - 60 - 20 + 10) + (50 - 10 - 30 + 1) = 41 of 150
    assert read(ev, "span_time_share", spans=WORK, part="gil_wait") == pytest.approx(
        100 * 41 / 150)
    total = sum(read(ev, "span_time_share", spans=WORK, part=p)
                for p in ("run", "blocked", "gil_wait"))
    assert total == pytest.approx(100.0)


def test_a_span_under_another_listed_one_is_not_counted_twice(ev):
    both = read(ev, "span_time_share", spans=["call.TopN", "topn.prep"], part="run")
    assert both == read(ev, "span_time_share", spans=["call.TopN"], part="run") == 60.0
    # alone it is counted: 20 - 5 - 12 = 3 of 20 ms wanted to run and did not
    assert read(ev, "span_time_share", spans=["topn.prep"], part="gil_wait") == 15.0
    # plan.leaves: 10 - 0 - 2 of 10
    assert read(ev, "span_time_share", spans=["plan.leaves"], part="gil_wait") == 80.0


def test_no_more_wake_is_put_back_than_the_span_waited_on_a_queue(ev):
    # a parent whose own thread waited for its mappers (kind map): the
    # wake beneath it was another thread's, and stays out
    ev["traces"][0]["spans"].append(
        span("call.Count", 110.0, 5.0, 100.0, sid="cc", blocked={"map": 100.0}))
    ev["traces"][0]["spans"][0]["parent_id"] = "cc"
    assert read(ev, "span_time_share", spans=["call.Count"], part="gil_wait") == \
        pytest.approx(100 * 5 / 110)
    assert read(ev, "span_time_share", spans=["call.Count"], part="blocked") == \
        pytest.approx(100 * 100 / 110)


def test_a_remainder_below_zero_stays_in_the_sums(ev):
    # cpu_ms ticks: a span of 4 ms charged a whole 10 ms tick, beside one
    # of 16 ms charged none; span by span clipped the share would read 0
    ev = {"traces": [{"spans": [span("x", 4.0, 10.0, 0.0, sid="a"),
                                 span("x", 16.0, 0.0, 0.0, sid="b")]}]}
    assert read(ev, "span_time_share", spans=["x"], part="run") == 50.0
    assert read(ev, "span_time_share", spans=["x"], part="gil_wait") == 50.0


def test_a_program_that_records_no_blocked_ms_has_nothing_to_read(ev):
    for t in ev["traces"]:
        for s in t["spans"]:
            del s["blocked_ms"]
    for part in ("run", "blocked", "gil_wait"):
        assert read(ev, "span_time_share", spans=WORK, part=part) is None
    assert read(ev, "span_time_share", spans=["no_such_span"], part="run") is None
    assert read({"traces": []}, "span_time_share", spans=WORK, part="run") is None
    # and the medians of spans it never recorded
    assert read(ev, "span_median", span="handoff.queue") == 20.0
    ev["traces"] = [{"spans": [s for s in t["spans"]
                               if not s["name"].startswith("handoff.")]}
                    for t in ev["traces"]]
    assert read(ev, "span_median", span="handoff.queue") is None
    assert read(ev, "span_median", span="handoff.wake") is None


D = "pilosa_exec_dispatcher_"
LIFE = [D + "idleMs", D + "launchMs", D + "hostMs"]


def test_counter_share():
    ev = {"window": (100.0, 101.0), "metrics": {
        "before": {D + "idleMs": 1000.0, D + "launchMs": 500.0, D + "hostMs": 100.0},
        "after": {D + "idleMs": 1400.0, D + "launchMs": 1000.0, D + "hostMs": 200.0,
                  "pilosa_pool_lockWaitMs": 12.5}}}
    # grew 400 / 500 / 100 of 1000
    assert read(ev, "counter_share", num=[D + "launchMs"], den=LIFE) == 50.0
    assert read(ev, "counter_share", num=[D + "hostMs"], den=LIFE) == 10.0
    assert read(ev, "counter_share", num=LIFE, den=LIFE) == 100.0
    # with no den, of the window's own 1,000 ms: the same here, where the
    # closing scrape came as the window closed
    assert read(ev, "counter_share", num=[D + "launchMs"]) == 50.0
    # a traced run scrapes after /debug/profile has returned: 9 s of idle
    # more, and the share of the window stands where the share of the
    # growth falls
    ev["metrics"]["after"][D + "idleMs"] += 9000.0
    assert read(ev, "counter_share", num=[D + "launchMs"], den=LIFE) == 5.0
    assert read(ev, "counter_share", num=[D + "launchMs"]) == 50.0
    assert read(ev, "counter_share", num=[D + "hostMs"]) == 10.0
    # a series that was not there before the window counts from 0
    assert read(ev, "counter_delta", series="pilosa_pool_lockWaitMs") == 12.5
    # nothing grew: no share to give
    still = {"window": (100.0, 101.0),
             "metrics": {"before": ev["metrics"]["after"],
                         "after": ev["metrics"]["after"]}}
    assert read(still, "counter_share", num=[D + "launchMs"], den=LIFE) is None
    assert read(still, "counter_share", num=[D + "launchMs"]) == 0.0


def test_counter_share_on_a_program_without_the_series():
    ev = {"window": (0.0, 1.0),
          "metrics": {"before": {}, "after": {"pilosa_uptime_seconds": 3.0}}}
    assert read(ev, "counter_share", num=[D + "launchMs"], den=LIFE) is None
    assert read(ev, "counter_share", num=[D + "launchMs"]) is None
    assert read(ev, "counter_delta", series="pilosa_pool_lockWaitMs") is None


def test_the_new_metrics_are_found_by_name():
    ev = {"traces": [], "window": (0.0, 1.0), "metrics": {"before": {}, "after": {}}}
    for name in ("exec.handoff_queue_ms", "exec.handoff_wake_ms", "exec.run_share",
                 "exec.gil_wait_share", "exec.plan_leaves_gil_wait_share",
                 "exec.topn_prep_gil_wait_share", "exec.bsi_prep_gil_wait_share",
                 "exec.dispatcher_launch_share", "exec.dispatcher_host_share",
                 "exec.pool_lock_wait_ms"):
        assert metrics.layer_metric(name, ev) is None
