"""The reducers that ``nyc-taxi-rides.q3q4`` adds, on hand-made
evidence: ``taxi_roofline`` (the bytes a text asks for, from the
configuration and the text alone), ``span_tag_ratio`` and
``counter_per_answer``.  A program without the ``topn.src`` span and the
``topn.srcHostBytes`` counter (the parent of the PR that added them)
gives the last two nothing to read, and none of them raises."""

import copy
import json
import os

import pytest

import metrics
from conftest import BENCH

PEAK = 819e9  # TPU v5 lite HBM bytes a second (peaks.json)
PLANE = 131072  # one dense (slice, row) plane of 2^20 columns
Q3 = "TopN(Bitmap(frame=pickup_year, rowID={y}), frame=passenger_count, n=10)"
Q4 = ("TopN(Intersect(Bitmap(frame=pickup_year, rowID={y}), "
      "Bitmap(frame=passenger_count, rowID={p})), frame=dist_miles, n=64)")


def read(ev, reducer, **args):
    return metrics.load_reducer(reducer)(ev, **args)


def answer(i, text, start, score_cache="computed", fetch_ms=20.0):
    """A request's record and its trace: dispatch at ``start``, the
    fetch closing ``fetch_ms`` after it."""
    tid = f"t{i}"
    spans = [
        {"name": "topn.score", "span_id": f"s{i}", "parent_id": None, "start": start,
         "duration_ms": fetch_ms + 1.0, "tags": {"score_cache": score_cache}},
        {"name": "topn.dispatch", "span_id": f"d{i}", "parent_id": f"s{i}",
         "start": start, "duration_ms": 1.0, "tags": {}},
        {"name": "topn.fetch", "span_id": f"f{i}", "parent_id": f"s{i}",
         "start": start + 0.001, "duration_ms": fetch_ms, "tags": {}},
    ]
    record = {"kind": "read", "text": text, "trace_id": tid, "ok": True, "correct": True}
    return record, {"trace_id": tid, "spans": spans}


@pytest.fixture()
def cfg():
    with open(os.path.join(BENCH, "configs", "nyc-taxi-rides.json")) as f:
        return json.load(f)


def evidence(cfg, answers, busy_s):
    """A one-chip profile of 100-110 s in which programs ran ``busy_s``."""
    records, traces = [], []
    for i, (text, start, cache) in enumerate(answers):
        r, t = answer(i, text, start, cache)
        records.append(r)
        traces.append(t)
    return {
        "config": cfg, "records": records, "traces": traces,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        "profile": {"start": 100.0, "stop": 110.0,
                    "devices": {"/device:TPU:0": [["kernel", 101.0, busy_s]]}},
    }


def test_only_the_reached_slices_and_the_ranked_frames_rows_count(cfg):
    ev = evidence(cfg, [(Q3.format(y=2015), 102.0, "computed"),
                        (Q4.format(y=2009, p=9), 103.0, "computed")], 0.5)
    # Q3 of 2015: 10 passenger rows + the src, over the 82 slices of 2015;
    # Q4 of 2009: 64 distance rows + the src, over its 158 slices.  Not
    # the 16-row class of passenger_count, not 1,050 slices.
    need = (11 * 82 + 65 * 158) * PLANE
    assert need == 1_464_336_384
    got = read(ev, "taxi_roofline")
    assert got == pytest.approx(100.0 * need / PEAK / 0.5)
    assert got == pytest.approx(0.35760, rel=1e-4)


def test_a_program_that_streams_every_slice_reads_at_most_100(cfg):
    """The least a program that scores all 1,050 slices of a text's
    padded planes can take at the peak, and the share it reads."""
    answers = [(Q3.format(y=y), 101.0 + k, "computed") for k, y in enumerate(range(2009, 2016))]
    answers += [(Q4.format(y=y, p=p), 101.5 + k, "computed")
                for k, (y, p) in enumerate([(2009, 1), (2012, 7), (2015, 0)])]
    streamed = (7 * (16 + 1) + 3 * (64 + 1)) * 1050 * PLANE
    ev = evidence(cfg, answers, streamed / PEAK)
    got = read(ev, "taxi_roofline")
    assert 0 < got <= 100.0
    # about one slice in seven, of fewer rows than the padded class
    assert got == pytest.approx(
        100.0 * (sum(11 * r for r in (158, 157, 164, 166, 162, 167, 82))
                 + 65 * (158 + 166 + 82)) / (7 * 17 + 3 * 65) / 1050)


def test_nothing_to_read_without_a_counted_answer(cfg):
    # scores shared from the memo streamed nothing
    ev = evidence(cfg, [(Q3.format(y=2011), 102.0, "shared")], 0.5)
    assert read(ev, "taxi_roofline") is None
    # an answer whose dispatch opened before the profile
    ev = evidence(cfg, [(Q3.format(y=2011), 99.0, "computed")], 0.5)
    assert read(ev, "taxi_roofline") is None
    # an untraced request, a CPU run, no profile
    ev = evidence(cfg, [(Q3.format(y=2011), 102.0, "computed")], 0.5)
    assert read(ev, "taxi_roofline") is not None
    ev2 = copy.deepcopy(ev)
    ev2["records"][0]["trace_id"] = ""
    assert read(ev2, "taxi_roofline") is None
    ev2 = copy.deepcopy(ev)
    ev2["device"]["platform"] = "cpu"
    assert read(ev2, "taxi_roofline") is None
    ev2["device"]["platform"] = "tpu"
    ev2["profile"] = None
    assert read(ev2, "taxi_roofline") is None


def test_the_src_reach_and_the_host_bytes_an_answer():
    def src(slices, reached):
        return {"name": "topn.src", "tags": {"leaves": 1, "slices": slices,
                                             "reached": reached, "bytes": reached * PLANE}}

    ev = {"traces": [{"spans": [src(1050, 158)]}, {"spans": [src(1050, 82)]},
                     {"spans": [{"name": "topn.prep", "tags": {}}]}],
          "records": [{"ok": True}, {"ok": True}, {"ok": True}, {"ok": False}],
          "metrics": {"before": {"pilosa_topn_srcHostBytes_total": 1000.0},
                      "after": {"pilosa_topn_srcHostBytes_total": 1000.0 + 240 * PLANE}}}
    # (158 + 82) / (1050 + 1050)
    assert read(ev, "span_tag_ratio", span="topn.src", num="reached",
                den="slices") == pytest.approx(100.0 * 240 / 2100)
    # 240 rows of words over the 3 answers the window got
    assert read(ev, "counter_per_answer",
                series="pilosa_topn_srcHostBytes_total") == 80 * PLANE
    # the parent: no span, no counter
    parent = {"traces": [{"spans": [{"name": "topn.prep", "tags": {}}]}],
              "records": ev["records"], "metrics": {"before": {}, "after": {}}}
    assert read(parent, "span_tag_ratio", span="topn.src", num="reached",
                den="slices") is None
    assert read(parent, "counter_per_answer",
                series="pilosa_topn_srcHostBytes_total") is None
