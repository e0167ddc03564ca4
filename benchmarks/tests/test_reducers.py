"""Each reducer on a small hand-made evidence file
(``fixtures/evidence.json``: three traced requests and a failed one, a
two-device profile of a 4 s window), pinned to values worked out by
hand in the comments."""

import json
import os

import pytest

import metrics
import xplane
from conftest import BENCH, HERE


@pytest.fixture()
def ev():
    with open(os.path.join(HERE, "fixtures", "evidence.json")) as f:
        return json.load(f)


def read(ev, reducer, **args):
    return metrics.load_reducer(reducer)(ev, **args)


def test_percentile_is_linear_between_ranks():
    assert metrics.percentile([1, 2, 3, 4], 50) == 2.5
    assert metrics.percentile([10], 99) == 10
    assert metrics.percentile([0, 10], 90) == 9.0


def test_span_median_and_self_time(ev):
    # plan spans: 700, 0.2, 0.4 -> median 0.4
    assert read(ev, "span_median", span="plan") == 0.4
    # coalesce less its device child: 50-10, 30-0, 20-5 -> 40, 30, 15
    assert read(ev, "span_median", span="coalesce", self_time=True) == 30.0
    assert read(ev, "span_median", span="nothing_of_that_name") is None


def test_span_tag_share(ev):
    share = read(ev, "span_tag_share", span="plan", tag="batch_cache", value="hit")
    assert share == pytest.approx(100.0 * 2 / 3)


def test_client_minus_span(ev):
    # 810-790, 50-36, 40-28 -> 20, 14, 12; the failed request has no trace
    assert read(ev, "client_minus_span", span="execute") == 14.0


def test_client_percentile_leaves_failures_out(ev):
    # ok latencies 810, 50, 40; the failed request is left out
    assert read(ev, "client_percentile", q=50) == 50.0
    assert read(ev, "client_percentile", q=50, kind="write") is None


def test_end_to_end(ev):
    # 3 correct answers over a 4 s window
    assert metrics.answers_per_s(ev) == 0.75


def test_perf_ratio_and_counters(ev):
    # queries (13-4)+(5-0) = 14 over launches (10-4)+(2-0) = 8
    got = read(ev, "perf_ratio", sites=["coalesce", "interp", "total"],
               num="queries", den="launches")
    assert got == 14 / 8
    # unless said, the sites a Count can ride on
    assert metrics.COUNT_SITES == ("direct", "coalesce", "interp", "total", "collective")
    assert read(ev, "perf_ratio", num="queries", den="launches") == got
    assert read(ev, "counter_delta", series="pilosa_exec_programCache_entries") == 2.0
    assert read(ev, "counter_delta", series="absent") is None
    assert read(ev, "metric_max", prefix="pilosa_device_",
                suffix="_hbm_bytes_in_use") == 9.7e9
    assert read(ev, "setup_phase", phase="load_s") == 24.0


def test_device_busy_idle_and_gaps(ev):
    ops0 = ev["profile"]["devices"]["/device:TPU:0"]
    assert xplane.union(ops0) == [(100.5, pytest.approx(100.8)), (102.0, 102.1)]
    assert xplane.busy_s(ops0) == pytest.approx(0.4)
    # idle share of the worst device: device 1, 1 - 0.2/4 = 95 %
    assert read(ev, "device_idle") == pytest.approx(95.0)
    gaps = xplane.gaps(ops0, 100.0, 104.0)
    assert gaps[0] == (102.1, 104.0)  # the longest first
    assert sum(b - a for a, b in gaps) == pytest.approx(4.0 - 0.4)
    assert xplane.top_ops(ops0)[0] == ["fusion.1", pytest.approx(0.3)]


def test_count_roofline(ev):
    # 3 answers came inside the traced window; each needs 2 leaves x 954
    # slices x 131072 B = 250,085,376 B -> 750,256,128 B; two chips at
    # 819e9 B/s -> 0.458032 ms; mean busy (0.4 + 0.2)/2 = 0.3 s.
    need = 3 * 2 * 954 * 131072
    assert need == 750_256_128
    want = 100.0 * (need / (2 * 819e9)) / 0.3
    assert read(ev, "count_roofline", leaves=2) == pytest.approx(want)
    assert want == pytest.approx(0.15268, rel=1e-4)


def test_count_roofline_with_no_reply_inside_the_profile(ev):
    # What the four-chip cell's traced run met: every request outlasted
    # the profile.  Nothing to read, and never 0 for a share.
    for r in ev["records"]:
        r["done"] = ev["profile"]["stop"] + 1.0
    assert read(ev, "count_roofline") is None
    assert read(ev, "device_idle") is not None


def test_a_cpu_run_reads_no_device_metric(ev):
    ev["device"]["platform"] = "cpu"
    assert read(ev, "device_idle") is None
    assert read(ev, "count_roofline") is None
    ev["device"]["platform"] = "tpu"
    ev["profile"] = None
    assert read(ev, "device_idle") is None


def test_the_peaks_table_refuses_an_unknown_kind(ev):
    assert metrics.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        metrics.peak("TPU v9 imaginary")
    ev["device"]["kind"] = "TPU v9 imaginary"
    with pytest.raises(KeyError):
        read(ev, "count_roofline")


def test_every_per_layer_metric_is_a_file_with_a_reducer():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        with open(os.path.join(BENCH, "layer_metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        # unit, layer and moves stand in BENCHMARK.json alone
        assert set(spec) <= {"reducer", "args"}
        assert callable(metrics.load_reducer(spec["reducer"]))


def test_breakdown_gives_each_idle_gap_to_the_innermost_open_span(ev):
    import run

    bd = run.breakdown(ev)
    # device 0 is the busiest.  Idle: 100.0-100.5 (middle 100.25, inside
    # request 1's plan span), 100.8-102.0 and 102.1-104.0 (no request open
    # at 101.4 or 103.05).
    assert bd["device_ops"][0] == ["fusion.1", pytest.approx(0.3)]
    gaps = dict(bd["idle_gaps"])
    assert gaps["plan"] == pytest.approx(0.5)
    assert gaps["no_request"] == pytest.approx(1.2 + 1.9)
