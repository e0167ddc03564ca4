"""Metric arithmetic: the end-to-end metrics, the peaks table, and the
loader that finds a per-layer metric's file and its reducer by name.

A per-layer metric is ``layer_metrics/<name>.json``: ``reducer`` (a
module ``reducers/<reducer>.py`` with ``read(ev, **args)``) and its
``args``; its unit, layer and the metric it moves stand in
``BENCHMARK.json`` alone.  A reducer that finds nothing to read returns
``None`` and the metric is left out of the line.
"""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))

# Launch sites of obs/perf.py on which a Count can ride: the default of
# ``reducers/perf_ratio.py`` and the ``SITES`` of the kind ``two-row-count``.
COUNT_SITES = ("direct", "coalesce", "interp", "total", "collective")


def percentile(values: list[float], q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no values")
    k = (len(xs) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def peak(device_kind: str) -> dict:
    """The published peaks of one chip; an unknown kind is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["by_device_kind"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}: add it to "
            "benchmarks/peaks.json with its source"
        )
    return table[device_kind]


# ---------------------------------------------------------------------------
# end-to-end metrics: what a client of the server sees, host clock
# ---------------------------------------------------------------------------


def answers_per_s(ev: dict) -> float:
    """Correct answers over the whole window: from the first request to
    the last reply to a request sent before the close."""
    w0, w1 = ev["window"]
    return sum(1 for r in ev["records"] if r["correct"]) / (w1 - w0)


def setup_s(ev: dict) -> float:
    return ev["setup"]["setup_s"]


END_TO_END = {
    "answers_per_s": answers_per_s,
    "setup_s": setup_s,
}


# ---------------------------------------------------------------------------
# per-layer metrics, found by name
# ---------------------------------------------------------------------------


def load_reducer(name: str):
    path = os.path.join(HERE, "reducers", name + ".py")
    spec = importlib.util.spec_from_file_location("reducers_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def layer_metric(name: str, ev: dict) -> float | None:
    """The per-layer metric ``name``, or ``None`` with nothing to read."""
    with open(os.path.join(HERE, "layer_metrics", name + ".json")) as f:
        spec = json.load(f)
    value = load_reducer(spec["reducer"])(ev, **spec.get("args", {}))
    return None if value is None else float(value)


# ---------------------------------------------------------------------------
# helpers the reducers share
# ---------------------------------------------------------------------------


def spans_named(ev: dict, name: str) -> list[dict]:
    return [s for t in ev["traces"] for s in t["spans"] if s["name"] == name]


def children_ms(trace: dict, span: dict) -> float:
    return sum(
        s["duration_ms"] or 0.0 for s in trace["spans"]
        if s.get("parent_id") == span["span_id"]
    )
