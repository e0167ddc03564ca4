"""Performance-observability tests (obs/perf.py + the /debug/perf |
/debug/profile | /debug/stacks endpoints + the native latency histogram
families).

Covers the PR-17 acceptance bar: per-site launch accounting visible
at /debug/perf for the direct / coalesce / interp / collective / topn
launch sites; lifetime-monotonic histogram
``_count``/``_sum`` past the reservoir size; StatsD truncation at
UTF-8 codepoint boundaries; /metrics exposition validity under a
concurrent scrape-vs-writer storm; launch byte accounting consistent
with /debug/hbm plane geometry; profiling endpoints end-to-end
including the 501 path; and the telemetry overhead guard (on-vs-off
query p99 within 5%).
"""

from __future__ import annotations

import concurrent.futures
import json
import re
import socket
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from pilosa_tpu import config as config_mod
from pilosa_tpu.cluster.topology import new_cluster
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.exec import Executor, plan
from pilosa_tpu.exec.coalesce import CoalesceScheduler
from pilosa_tpu.net import handler as handler_mod
from pilosa_tpu.net.client import InternalClient
from pilosa_tpu.net.handler import Handler, Request
from pilosa_tpu.net.server import Server
from pilosa_tpu.obs import perf, prom
from pilosa_tpu.obs import stats as stats_mod
from pilosa_tpu.ops.bitplane import SLICE_WIDTH, WORDS_PER_SLICE
from pilosa_tpu.pql.parser import parse_string

ROW_SLOT_BYTES = WORDS_PER_SLICE * 4  # one plane row = 128 KiB

WAIT_US = 200_000


@pytest.fixture(autouse=True)
def _fresh_registry():
    """The perf registry is process-global (like the device pool) —
    isolate every test from its neighbors' launches."""
    perf.registry().reset()
    perf.registry().configure(enabled=True)
    yield
    perf.registry().reset()
    perf.registry().configure(enabled=True)


@pytest.fixture
def holder(tmp_path):
    h = Holder(str(tmp_path / "data"))
    h.open()
    yield h
    h.close()


@pytest.fixture
def ex(holder):
    c = new_cluster(1)
    return Executor(holder, host=c.nodes[0].host, cluster=c)


def must_set_bits(holder, index, frame, bits, view="standard"):
    idx = holder.create_index_if_not_exists(index)
    f = idx.create_frame_if_not_exists(frame)
    for row, col in bits:
        f.set_bit(view, row, col)
    return f


def q(ex, index, pql):
    return ex.execute(index, parse_string(pql), None, None)


# ---------------------------------------------------------------------------
# registry units
# ---------------------------------------------------------------------------


class TestPerfRegistry:
    def test_plane_bytes_geometry(self):
        assert perf.plane_bytes(1, WORDS_PER_SLICE) == ROW_SLOT_BYTES
        assert perf.plane_bytes(3, 64) == 3 * 64 * 4

    def test_record_snapshot_and_gauges(self):
        r = perf.registry()
        # 1 GB in 0.101 s on the host's clock = 9.9 GB/s.
        r.record_launch(
            "coalesce", reduce="count", queries=4, rows=8,
            n_bytes=1_000_000_000, dispatch_ms=20.0, total_ms=100.0,
            trace_id="t1",
        )
        r.record_launch(
            "coalesce", reduce="row", queries=2, rows=2,
            n_bytes=0, total_ms=1.0, trace_id="t2",
        )
        snap = r.snapshot()
        site = snap["sites"]["coalesce"]
        assert site["launches"] == 2
        assert site["queries"] == 6
        assert site["occupancy"] == 3.0
        assert site["bytes"] == 1_000_000_000
        assert site["gbps"] == pytest.approx(1.0 / 0.101, rel=1e-3)
        assert site["reduces"] == {"count": 1, "row": 1}
        assert site["p99_ms"] > site["p50_ms"] > 0
        # Slowest table keeps the trace id for /debug/traces handoff.
        assert snap["slowest"][0]["trace_id"] == "t1"
        g = r.gauges()
        assert g["exec.launch.launches[site:coalesce]"] == 2
        assert g["exec.launch.gbps[site:coalesce]"] == site["gbps"]

    def test_disabled_registry_records_nothing(self):
        r = perf.registry()
        r.configure(enabled=False)
        r.record_launch("direct", n_bytes=5, total_ms=1.0)
        assert r.snapshot()["sites"] == {}

    def test_module_shorthand_and_trace_id_outside_span(self):
        assert perf.current_trace_id() == ""
        perf.record_launch("topn", reduce="topn", total_ms=2.0)
        assert perf.registry().snapshot()["sites"]["topn"]["launches"] == 1


# ---------------------------------------------------------------------------
# native latency histograms + SLO burn
# ---------------------------------------------------------------------------


class TestLatencyHistograms:
    def test_cumulative_buckets_sum_count(self):
        lh = perf.LatencyHistograms(buckets_ms=[10.0, 100.0])
        for ms in (1.0, 5.0, 50.0, 500.0):
            lh.observe_query("point", ms)
        text = lh.render()
        assert "# TYPE pilosa_query_latency_ms histogram" in text
        assert 'pilosa_query_latency_ms_bucket{class="point",le="10"} 2' in text
        assert 'pilosa_query_latency_ms_bucket{class="point",le="100"} 3' in text
        assert 'pilosa_query_latency_ms_bucket{class="point",le="+Inf"} 4' in text
        assert 'pilosa_query_latency_ms_count{class="point"} 4' in text
        assert 'pilosa_query_latency_ms_sum{class="point"} 556' in text

    def test_http_family_keyed_by_route_template(self):
        lh = perf.LatencyHistograms()
        lh.observe_http("GET", "/index/{index}/query", 3.0)
        text = lh.render()
        assert (
            'pilosa_http_latency_ms_count{method="GET",'
            'path="/index/{index}/query"} 1'
        ) in text

    def test_slo_gauges_and_burn_rate(self):
        lh = perf.LatencyHistograms(
            buckets_ms=[10.0], slo_ms=10.0, slo_objective=0.9
        )
        for _ in range(8):
            lh.observe_query("heavy", 1.0)
        for _ in range(2):
            lh.observe_query("heavy", 100.0)  # 20% error, 10% budget
        text = lh.render()
        assert "pilosa_obs_slo_target_ms 10" in text
        assert "pilosa_obs_slo_objective 0.9" in text
        m = re.search(
            r'pilosa_obs_slo_error_rate\{class="heavy"\} ([0-9.]+)', text
        )
        assert m and float(m.group(1)) == pytest.approx(0.2)
        m = re.search(
            r'pilosa_obs_slo_burn_rate\{class="heavy"\} ([0-9.]+)', text
        )
        assert m and float(m.group(1)) == pytest.approx(2.0, rel=1e-3)

    def test_no_slo_no_slo_gauges(self):
        lh = perf.LatencyHistograms()
        lh.observe_query("point", 1.0)
        assert "slo" not in lh.render()

    def test_empty_render_is_empty(self):
        assert perf.LatencyHistograms().render() == ""


def test_route_template_normalization():
    assert (
        handler_mod._route_template(r"/index/(?P<index>[^/]+)/query")
        == "/index/{index}/query"
    )
    assert handler_mod._route_template(r"/metrics") == "/metrics"


# ---------------------------------------------------------------------------
# satellite 1: lifetime-monotonic histogram count/sum past the reservoir
# ---------------------------------------------------------------------------


class TestHistogramLifetimeTotals:
    def test_count_sum_monotonic_past_reservoir(self):
        c = stats_mod.ExpvarStatsClient()
        n = 5000  # > the 4096 reservoir
        for i in range(n):
            c.histogram("lat", float(i % 10))
        h = c.snapshot()["histograms"]["lat"]
        assert h["count"] == n
        assert h["sum"] == pytest.approx(sum(float(i % 10) for i in range(n)))
        # The windowed reservoir is still bounded.
        assert h["n"] <= 4096
        # One more observation: lifetime totals keep growing (the bug
        # this guards: reservoir-derived _count capped at 4096 breaks
        # Prometheus rate()).
        c.histogram("lat", 3.0)
        h2 = c.snapshot()["histograms"]["lat"]
        assert h2["count"] == n + 1
        assert h2["sum"] == pytest.approx(h["sum"] + 3.0)

    def test_prom_render_uses_lifetime_totals(self):
        c = stats_mod.ExpvarStatsClient()
        for i in range(4200):
            c.histogram("lat", 1.0)
        text = prom.render(c.snapshot())
        assert "pilosa_lat_count 4200" in text
        assert "pilosa_lat_sum 4200" in text

    def test_prom_render_legacy_snapshot_fallback(self):
        # A snapshot without lifetime totals (older producer) still
        # renders, deriving sum from the windowed mean.
        text = prom.render(
            {"histograms": {"lat": {"n": 4, "mean": 2.5, "min": 1.0,
                                    "max": 4.0, "p50": 2.5, "p90": 3.7,
                                    "p99": 3.97, "p999": 3.997}}}
        )
        assert "pilosa_lat_count 4" in text
        assert "pilosa_lat_sum 10" in text


# ---------------------------------------------------------------------------
# satellite 2: StatsD truncation at UTF-8 codepoint boundaries
# ---------------------------------------------------------------------------


class TestStatsDUtf8Truncation:
    def test_truncation_never_splits_a_codepoint(self):
        rx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        rx.bind(("127.0.0.1", 0))
        rx.settimeout(2.0)
        port = rx.getsockname()[1]
        # 3-byte codepoints positioned so the 1432-byte cut lands
        # mid-rune for naive byte slicing.
        tags = [f"tag{i}:{'日本語' * 20}" for i in range(40)]
        c = stats_mod.StatsDClient(f"127.0.0.1:{port}").with_tags(*tags)
        try:
            c.count("bits", 1)
            data, _ = rx.recvfrom(65536)
            assert len(data) <= stats_mod.StatsDClient.MAX_PAYLOAD
            # The payload must decode — a mid-rune cut raises here.
            data.decode("utf-8")
            assert data.startswith(b"pilosa.bits:1|c")
        finally:
            rx.close()
            c.close()

    def test_cut_walks_back_over_continuation_bytes(self):
        # Unit-level: craft a payload whose MAX_PAYLOAD'th byte is a
        # continuation byte and check the boundary logic directly.
        base = "x" * (stats_mod.StatsDClient.MAX_PAYLOAD - 1) + "日"
        data = base.encode()
        cut = stats_mod.StatsDClient.MAX_PAYLOAD
        while cut > 0 and (data[cut] & 0xC0) == 0x80:
            cut -= 1
        assert data[:cut].decode("utf-8") == "x" * (
            stats_mod.StatsDClient.MAX_PAYLOAD - 1
        )


# ---------------------------------------------------------------------------
# satellite 3a: /metrics validity under a concurrent scrape-vs-writer storm
# ---------------------------------------------------------------------------

# Label VALUES may legally contain braces (e.g. the http route
# template path="/index/{index}/query"), so the label block is matched
# greedily to the last "}".
_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{.*\})? [-+0-9.einfa]+$"
)


def _assert_valid_exposition(text: str) -> None:
    assert text.endswith("\n")
    seen_types: dict[str, str] = {}
    seen_samples: set[str] = set()
    for line in text.rstrip("\n").split("\n"):
        if line.startswith("# TYPE "):
            _, _, fam, kind = line.split(" ")
            assert kind in ("counter", "gauge", "summary", "histogram"), line
            assert fam not in seen_types, f"duplicate # TYPE for {fam}"
            seen_types[fam] = kind
        else:
            assert _SAMPLE_RE.match(line), f"bad exposition line: {line!r}"
            key = line.rsplit(" ", 1)[0]
            assert key not in seen_samples, f"duplicate series: {key}"
            seen_samples.add(key)


class TestScrapeWriterStorm:
    def test_exposition_valid_under_concurrent_writes(self):
        c = stats_mod.ExpvarStatsClient()
        lh = perf.LatencyHistograms(slo_ms=5.0)
        stop = threading.Event()
        errs: list[BaseException] = []

        def writer(i: int):
            tagged = c.with_tags(f"index:i{i % 3}")
            j = 0
            try:
                while not stop.is_set():
                    tagged.count("storm.writes", 1)
                    tagged.histogram("storm.lat", float(j % 50))
                    c.gauge(f"storm.g{i}", float(j))
                    lh.observe_query(f"class{i % 2}", float(j % 20))
                    lh.observe_http("GET", "/metrics", 0.1)
                    perf.record_launch(
                        "coalesce", reduce="count", n_bytes=1024,
                        total_ms=0.01,
                    )
                    j += 1
            except BaseException as e:  # noqa: BLE001
                errs.append(e)

        threads = [
            threading.Thread(target=writer, args=(i,)) for i in range(4)
        ]
        for t in threads:
            t.start()
        try:
            deadline = time.monotonic() + 1.0
            scrapes = 0
            while time.monotonic() < deadline:
                text = prom.render(
                    c.snapshot(),
                    extra_gauges=perf.registry().gauges(),
                )
                text += lh.render()
                _assert_valid_exposition(text)
                scrapes += 1
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=10)
        assert not errs
        assert scrapes > 3
        # Final state: every writer family landed.
        final = prom.render(c.snapshot()) + lh.render()
        assert "pilosa_storm_writes_total" in final
        assert "pilosa_query_latency_ms_bucket" in final
        assert "pilosa_obs_slo_burn_rate" in final


# ---------------------------------------------------------------------------
# launch-site instrumentation through the coalescer
# ---------------------------------------------------------------------------


class TestCoalescerSites:
    def test_coalesce_and_interp_sites_record(self, rng):
        co = CoalesceScheduler(max_wait_us=WAIT_US)
        try:
            words = 64
            b = jnp.asarray(
                rng.integers(0, 2**32, size=(4, 2, words), dtype=np.uint32)
            )
            expr = ("Intersect", ("leaf", 0), ("leaf", 1))
            # Same program key twice -> one coalesced launch.
            futs = [co.submit(expr, "count", b) for _ in range(2)]
            for f in futs:
                f.result(timeout=30)
            # Distinct exprs -> fused interpreter launch.
            exprs = [
                ("Intersect", ("leaf", 0), ("leaf", 1)),
                ("Union", ("leaf", 0), ("leaf", 1)),
                ("Xor", ("leaf", 0), ("leaf", 1)),
            ]
            futs = [co.submit(e, "count", b) for e in exprs]
            for f in futs:
                f.result(timeout=30)
        finally:
            co.close()
        sites = perf.registry().snapshot()["sites"]
        assert sites["coalesce"]["launches"] >= 1
        assert sites["coalesce"]["queries"] >= 2
        # Logical bytes: pre-pad rows x words x 4.
        assert sites["coalesce"]["bytes"] % (words * 4) == 0
        assert sites["interp"]["launches"] >= 1
        assert sites["interp"]["queries"] >= 3
        assert sites["interp"]["device_ms"] > 0

    def test_total_reduce_site_records(self, rng):
        co = CoalesceScheduler(max_wait_us=0)
        try:
            b = jnp.asarray(
                rng.integers(0, 2**32, size=(2, 2, 64), dtype=np.uint32)
            )
            fut = co.submit(
                ("Intersect", ("leaf", 0), ("leaf", 1)), "total", b
            )
            fut.result(timeout=30)
        finally:
            co.close()
        sites = perf.registry().snapshot()["sites"]
        # Mesh present (virtual 8-device conftest) -> the ICI-reduced
        # collective site; single-device fallback -> "total".
        assert ("collective" in sites) or ("total" in sites)


# ---------------------------------------------------------------------------
# compile-time accounting
# ---------------------------------------------------------------------------


def test_program_cache_compile_ms_accumulates(rng):
    plan.clear_program_caches()
    co = CoalesceScheduler(max_wait_us=0)
    try:
        b = jnp.asarray(
            rng.integers(0, 2**32, size=(2, 2, 64), dtype=np.uint32)
        )
        co.submit(
            ("Intersect", ("leaf", 0), ("leaf", 1)), "count", b
        ).result(timeout=30)
    finally:
        co.close()
    ms = plan.program_cache_compile_ms()
    assert ms and all(v >= 0 for v in ms.values())
    plan.clear_program_caches()
    assert plan.program_cache_compile_ms() == {}


# ---------------------------------------------------------------------------
# single-node integration: the endpoints
# ---------------------------------------------------------------------------


@pytest.fixture
def perf_server(tmp_path):
    s = Server(
        data_dir=str(tmp_path / "data"),
        stats=stats_mod.ExpvarStatsClient(),
        slo_ms=50.0,
        coalesce_max_wait_us=WAIT_US,
        anti_entropy_interval=3600,
        polling_interval=3600,
        cache_flush_interval=3600,
    )
    s.open()
    yield s
    s.close()


def _populate(s, rows=2, cols=(5, 9, SLICE_WIDTH + 3)):
    s.holder.create_index_if_not_exists("i")
    f = s.holder.index("i").create_frame_if_not_exists("f")
    for r in range(1, rows + 1):
        for col in cols:
            f.set_bit("standard", r, col + r)
    return f


class TestPerfEndpoint:
    def test_all_launch_sites_reported(self, perf_server):
        s = perf_server
        _populate(s)
        c = InternalClient(s.host, timeout=30.0)
        # topn site: the src bitmap forces the fused device scorer (a
        # bare TopN can answer straight from the ranked cache).
        c.execute_pql("i", "TopN(Bitmap(rowID=1, frame=f), frame=f, n=2)")
        # collective (mesh total-count) or total site.
        assert c.execute_pql("i", 'Count(Bitmap(frame="f", rowID=1))') == 3
        # coalesce site (row reduce through the scheduler).
        c.execute_pql("i", 'Bitmap(frame="f", rowID=1)')
        # interp site: a concurrent burst of DISTINCT row-reduce trees
        # sharing the dispatch window fuses into interpreter launches
        # (Count trees would take the collective path instead).
        pqls = [
            'Intersect(Bitmap(frame="f", rowID=1),'
            ' Bitmap(frame="f", rowID=2))',
            'Union(Bitmap(frame="f", rowID=1),'
            ' Bitmap(frame="f", rowID=2))',
            'Difference(Bitmap(frame="f", rowID=1),'
            ' Bitmap(frame="f", rowID=2))',
        ]
        with concurrent.futures.ThreadPoolExecutor(len(pqls)) as pool:
            list(pool.map(lambda p: c.execute_pql("i", p), pqls))
        # direct site: the uncoalesced executor path.
        co, s.executor.coalescer = s.executor.coalescer, None
        try:
            c.execute_pql("i", 'Bitmap(frame="f", rowID=2)')
        finally:
            s.executor.coalescer = co

        status, data, _ = c._request_meta("GET", "/debug/perf")
        assert status == 200
        doc = json.loads(data)
        assert doc["enabled"] is True
        sites = doc["sites"]
        for site in ("direct", "coalesce", "interp", "topn"):
            assert site in sites, f"missing site {site}: {sorted(sites)}"
        assert ("collective" in sites) or ("total" in sites)
        for name, row in sites.items():
            assert row["launches"] >= 1, (name, row)
            assert row["gbps"] >= 0
            assert row["queries"] >= row["launches"], (name, row)
            assert row["dispatch_ms"] <= row["device_ms"] + 1e-6
        assert isinstance(doc["compile_ms"], dict)
        # Slowest launches carry trace ids for /debug/traces handoff.
        assert doc["slowest"]
        assert any(r["trace_id"] for r in doc["slowest"])

    def test_byte_accounting_matches_hbm_plane_geometry(self, perf_server):
        s = perf_server
        # a column at the slice's end: the plane is of full width
        f = _populate(s, rows=1, cols=(1, 7, SLICE_WIDTH - 2))
        c = InternalClient(s.host, timeout=30.0)
        c.execute_pql("i", 'Bitmap(frame="f", rowID=1)')
        sites = perf.registry().snapshot()["sites"]
        launch = sites.get("coalesce") or sites.get("direct")
        assert launch is not None
        # Per-row bytes must equal the 128 KiB row-slot /debug/hbm
        # reports planes in — same words-per-slice geometry end to end.
        assert launch["rows"] >= 1
        assert launch["bytes"] == launch["rows"] * ROW_SLOT_BYTES
        status, data, _ = c._request_meta("GET", "/debug/hbm")
        assert status == 200
        hbm = json.loads(data)
        frag_rows = hbm.get("fragments", [])
        assert frag_rows, hbm
        # The resident device bytes for the launch's planes can only be
        # >= the logical (pre-pad) bytes perf accounted: device-side
        # padding and shard round-up add, never subtract.
        assert launch["bytes"] <= sum(r["bytes"] for r in frag_rows)

    def test_metrics_carries_perf_gauges_and_histograms(self, perf_server):
        s = perf_server
        _populate(s)
        c = InternalClient(s.host, timeout=30.0)
        assert c.execute_pql("i", 'Count(Bitmap(frame="f", rowID=1))') == 3
        status, data, _ = c._request_meta("GET", "/metrics")
        assert status == 200
        text = data.decode()
        _assert_valid_exposition(text)
        assert re.search(r'pilosa_exec_launch_gbps\{site="', text), text
        assert re.search(r'pilosa_exec_launch_launches\{site="', text), text
        assert "# TYPE pilosa_query_latency_ms histogram" in text
        assert 'pilosa_query_latency_ms_bucket{class=' in text
        assert 'le="+Inf"' in text
        assert re.search(
            r'pilosa_http_latency_ms_count\{method="POST",'
            r'path="/index/\{index\}/query"\}', text
        ), text
        assert "pilosa_obs_slo_target_ms 50" in text
        assert "pilosa_obs_slo_burn_rate" in text

    def test_stacks_endpoint(self, perf_server):
        c = InternalClient(perf_server.host, timeout=30.0)
        status, data, headers = c._request_meta("GET", "/debug/stacks")
        assert status == 200
        assert headers["content-type"].startswith("text/plain")
        text = data.decode()
        assert "MainThread" in text
        assert "threads" in text.splitlines()[0]

    def test_profile_endpoint_end_to_end(self, perf_server, tmp_path):
        c = InternalClient(perf_server.host, timeout=60.0)
        status, data, _ = c._request_meta(
            "GET", "/debug/profile?seconds=0.05"
        )
        if status == 501:
            # Runtime without xprof support: the endpoint must say so,
            # not 500.  (CI containers have it; this guards minimal
            # installs.)
            return
        assert status == 200
        doc = json.loads(data)
        assert doc["seconds"] == pytest.approx(0.05)
        assert doc["trace"].endswith(".tar.gz")
        assert doc["bytes"] > 0
        # The tarball lands under the server's data dir.
        assert doc["trace"].startswith(perf_server.data_dir)

    def test_profile_501_when_profiler_missing(self, perf_server, monkeypatch):
        monkeypatch.setattr(handler_mod, "_jax_profiler", lambda: None)
        c = InternalClient(perf_server.host, timeout=30.0)
        status, data, _ = c._request_meta("GET", "/debug/profile?seconds=0.05")
        assert status == 501
        assert b"unavailable" in data

    def test_profile_bad_seconds_400(self, perf_server):
        c = InternalClient(perf_server.host, timeout=30.0)
        status, _, _ = c._request_meta("GET", "/debug/profile?seconds=junk")
        assert status == 400

    def test_profile_single_flight_409(self, perf_server):
        h = perf_server.handler
        assert h._profile_mu.acquire(blocking=False)
        try:
            c = InternalClient(perf_server.host, timeout=30.0)
            status, _, _ = c._request_meta(
                "GET", "/debug/profile?seconds=0.05"
            )
            assert status == 409
        finally:
            h._profile_mu.release()


# ---------------------------------------------------------------------------
# config plumbing
# ---------------------------------------------------------------------------


class TestObsConfig:
    def test_toml_roundtrip(self):
        cfg = config_mod.from_toml(
            "[obs]\n"
            "latency-buckets-ms = [5.0, 50.0, 500.0]\n"
            "slo-ms = 100.0\n"
            "slo-objective = 0.99\n"
        )
        cfg.validate()
        assert cfg.obs.latency_buckets_ms == [5.0, 50.0, 500.0]
        assert cfg.obs.slo_ms == 100.0
        assert cfg.obs.slo_objective == 0.99
        cfg2 = config_mod.from_toml(cfg.to_toml())
        assert cfg2.obs.latency_buckets_ms == [5.0, 50.0, 500.0]

    def test_env_overlay(self):
        cfg = config_mod.apply_env(
            config_mod.Config(),
            {
                "PILOSA_OBS_LATENCY_BUCKETS_MS": "1,10,100",
                "PILOSA_OBS_SLO_MS": "25",
                "PILOSA_OBS_SLO_OBJECTIVE": "0.95",
            },
        )
        assert cfg.obs.latency_buckets_ms == [1.0, 10.0, 100.0]
        assert cfg.obs.slo_ms == 25.0
        assert cfg.obs.slo_objective == 0.95

    def test_validation_rejects_bad_values(self):
        cfg = config_mod.Config()
        cfg.obs.latency_buckets_ms = [10.0, 5.0]
        with pytest.raises(config_mod.ConfigError):
            cfg.validate()
        cfg = config_mod.Config()
        cfg.obs.latency_buckets_ms = [0.0, 5.0]
        with pytest.raises(config_mod.ConfigError):
            cfg.validate()
        cfg = config_mod.Config()
        cfg.obs.slo_objective = 1.0
        with pytest.raises(config_mod.ConfigError):
            cfg.validate()


# ---------------------------------------------------------------------------
# satellite 6: overhead guard — telemetry on vs off
# ---------------------------------------------------------------------------


class TestOverheadGuard:
    def test_telemetry_overhead_within_5pct(self, ex, holder):
        must_set_bits(
            holder, "i", "f",
            [(1, c) for c in range(0, 64, 3)]
            + [(1, SLICE_WIDTH + 7)],
        )
        # A row-reduce query on the uncoalesced path: the launch (and
        # its record_launch) runs ON the query thread, so the guard
        # measures the telemetry's true cost.  A collective Count would
        # run the record on the watchdog's worker thread, where GIL
        # handoff jitter between worker and waiting query thread
        # dwarfs — and randomly amplifies — the microseconds under
        # test.
        ex.coalescer = None
        call = parse_string('Bitmap(frame="f", rowID=1)')

        def batch(enabled: bool, n: int, sink: list) -> None:
            perf.registry().configure(enabled=enabled)
            for _ in range(n):
                t0 = time.perf_counter()
                ex.execute("i", call, None, None)
                sink.append(time.perf_counter() - t0)

        def p99(samples: list) -> float:
            samples = sorted(samples)
            return samples[int(len(samples) * 0.99)]

        # Warm compile caches and both code paths off the clock.
        batch(True, 50, [])
        batch(False, 50, [])
        # Fine-grained interleaving: alternate small on/off batches so
        # machine drift (GC, turbo, noisy CI neighbors) lands in both
        # pools equally, then compare the POOLED per-mode p99.  The GC
        # is parked during timing — collector pauses are the dominant
        # tail noise at this query size and have nothing to do with the
        # telemetry under test.
        import gc

        def measure() -> tuple[float, float]:
            # Per-round p99s, compared at the calmest round per mode:
            # the container shows occasional ~3 ms scheduler stalls
            # that poison a pooled p99, while a REAL overhead
            # regression shifts every round's tail including the best
            # one.
            on_p99s: list = []
            off_p99s: list = []
            gc.collect()
            gc.disable()
            try:
                for _ in range(6):
                    a: list = []
                    b: list = []
                    batch(True, 100, a)
                    batch(False, 100, b)
                    on_p99s.append(p99(a))
                    off_p99s.append(p99(b))
            finally:
                gc.enable()
            return min(on_p99s), min(off_p99s)

        # Up to three measurement passes: a single pass's p99 is one
        # sample of the scheduler-noise tail, so a real <=5% budget
        # needs a retry to not flake — a genuine overhead regression
        # fails every pass.
        results = []
        try:
            for _ in range(3):
                on, off = measure()
                results.append((on, off))
                if on <= off * 1.05 + 100e-6:
                    return
        finally:
            perf.registry().configure(enabled=True)
        pytest.fail(
            "telemetry overhead too high in all passes: "
            + ", ".join(
                f"on p99 {on*1e3:.3f} ms vs off p99 {off*1e3:.3f} ms"
                for on, off in results
            )
        )


# ---------------------------------------------------------------------------
# what the always-on tracer costs a request: a span, and the hand-over's
# stamps (PR 38)
# ---------------------------------------------------------------------------


def span_cost_us(batches: int = 7, n: int = 2000) -> dict:
    """Microseconds, the calmest of ``batches`` batches of ``n``: one
    span opened, closed and recorded; and what a request that waits on
    one launch pays for the account of its wait — the ``submitted``
    stamp, the stamp before ``set_result``, the two hand-over spans
    recorded under its ``launch``, and the wait's blocked time."""
    from pilosa_tpu.exec import coalesce as coalesce_mod
    from pilosa_tpu.obs import trace

    tr = trace.Tracer()

    def timed(body) -> float:
        best = float("inf")
        for _ in range(batches):
            # a trace holds 512 spans: a fresh one a batch, or the later
            # records are dropped and cost nothing
            root = tr.start_trace("query")
            token = root.activate()
            try:
                t0 = time.perf_counter()
                body(root)
                best = min(best, time.perf_counter() - t0)
            finally:
                root.deactivate(token)
                tr.finish_root(root)
        return best / n * 1e6

    def spans(root):
        for _ in range(n // 8):
            for _ in range(8):
                with tr.span("stage"):
                    pass
            root.tracer._open[root.trace_id]["spans"].clear()

    def handover(root):
        for _ in range(n // 8):
            for _ in range(8):
                fut: concurrent.futures.Future = concurrent.futures.Future()
                item = coalesce_mod._Item(batch=None, future=fut, pin_keys=())
                item.submitted = time.monotonic()
                launch = root.add_child("launch", 0.0, 1.0)
                item.launch_span = launch
                launch.add_child("handoff.queue", 0.0, 1.0, leaf=True,
                                 dispatcher="idle")
                item.resolve(1, 1)
                coalesce_mod.await_result(fut, 1.0)
            root.tracer._open[root.trace_id]["spans"].clear()

    def launch_only(root):
        for _ in range(n // 8):
            for _ in range(8):
                fut: concurrent.futures.Future = concurrent.futures.Future()
                coalesce_mod._Item(batch=None, future=fut, pin_keys=())
                root.add_child("launch", 0.0, 1.0)
                fut.set_result(1)
                fut.result(1.0)
            root.tracer._open[root.trace_id]["spans"].clear()

    with_stamps, without = timed(handover), timed(launch_only)
    return {"span_us": timed(spans), "handover_us": with_stamps - without}


class TestSpanCost:
    def test_the_handovers_stamps_are_under_one_percent_of_a_light_request(self):
        """The lightest requests of the benchmark are ≈ 2.8 ms of Python
        (``count-repeat``, 358 answers a second under one GIL): the
        account of a waited launch must stay under 1 % of that, 28
        microseconds, and a span under 20 (on the sandbox's CPU it was
        5.7 before the stamps and is 5.8 with them: ``start`` comes from
        the monotonic stamp, which pays for the note of the thread's
        blocked total; on the chip's host a span is ≈ 24)."""
        cost = span_cost_us()
        print(f"span cost: {cost}")
        assert cost["handover_us"] < 28.0, cost
        assert cost["span_us"] < 20.0, cost
