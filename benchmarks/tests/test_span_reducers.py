"""The two reducers that read what a span records beyond its duration,
on hand-made spans: the values are worked out in the comments.  A
program without these spans (the parent of the PR that added them)
gives each reducer nothing to read, and none of them raises."""

import pytest

import metrics


def read(ev, reducer, **args):
    return metrics.load_reducer(reducer)(ev, **args)


def span(name, ms, cpu=None, **tags):
    return {"name": name, "span_id": name, "parent_id": None, "start": 0.0,
            "duration_ms": ms, "cpu_ms": cpu, "tags": tags}


@pytest.fixture()
def ev():
    return {"traces": [
        {"spans": [span("map.local", 100.0, 40.0), span("compile", 1700.0),
                   span("launch", 1750.0)]},
        # recorded for another thread: no cpu_ms, left out of both sums
        {"spans": [span("map.local", 300.0, 60.0), span("map.local", 50.0)]},
        {"spans": [span("compile", 300.0), span("plan", 5.0, 5.0)]},
    ]}


def test_span_cpu_share(ev):
    # (40 + 60) / (100 + 300) = 25 %; the span without cpu_ms is not counted
    assert read(ev, "span_cpu_share", span="map.local") == 25.0
    assert read(ev, "span_cpu_share", span="plan") == 100.0
    # launch spans carry no cpu_ms at all: nothing to read
    assert read(ev, "span_cpu_share", span="launch") is None
    assert read(ev, "span_cpu_share", span="nothing_of_that_name") is None


def test_span_cpu_share_on_a_program_that_records_no_cpu_ms(ev):
    for t in ev["traces"]:
        for s in t["spans"]:
            del s["cpu_ms"]
    assert read(ev, "span_cpu_share", span="map.local") is None


def test_span_total(ev):
    # 1700 + 300 over the window's traces
    assert read(ev, "span_total", span="compile") == 2000.0
    # traces, and no span of that name: nothing compiled, which is 0
    assert read(ev, "span_total", span="nothing_of_that_name") == 0.0
    # no trace at all: nothing to read
    assert read({"traces": []}, "span_total", span="compile") is None


def test_the_old_reducers_read_the_new_spans(ev):
    ev["traces"][0]["spans"].append(
        span("anchored.prepass", 2900.0, 900.0, outcome="declined_dense"))
    ev["traces"][1]["spans"].append(
        span("anchored.prepass", 1.0, 1.0, outcome="answered"))
    assert read(ev, "span_median", span="anchored.prepass") == 1450.5
    assert read(ev, "span_tag_share", span="anchored.prepass", tag="outcome",
                value="answered") == 50.0
    ev["metrics"] = {"after": {"pilosa_device_0_hbm_bytes_in_use": 9.0e9,
                               "pilosa_device_0_hbm_peak_bytes_in_use": 9.9e9,
                               "pilosa_device_1_hbm_peak_bytes_in_use": 9.7e9}}
    # the two suffixes do not match each other's series
    assert read(ev, "metric_max", prefix="pilosa_device_",
                suffix="_hbm_peak_bytes_in_use") == 9.9e9
    assert read(ev, "metric_max", prefix="pilosa_device_",
                suffix="_hbm_bytes_in_use") == 9.0e9
