"""CI smoke pass over bench.py: a tiny CPU-only run that asserts the
JSON artifact parses and carries the coalescer's counters plus the
``bsi`` tier (Range/Sum over integer bit-planes), the ``mixed_storm``
tier (distinct-query fusion counters present, zero errors at trivial
load, launches < queries), the ``cold_restart`` tier
(time-to-first-answer under lazy staging), and the program-cache
entries/bounds invariant — including the new ``interp`` family.

Not a performance measurement — a wiring check: the bench's executor
tiers must produce one valid JSON line on stdout with the coalesce
section (launches / occupancy / dispatches-per-query per concurrent
tier) and the bsi tier's Gcols/s + ms/query figures, so a refactor
cannot silently break the artifact the perf trajectory is built from.
Run via ``make bench-smoke``; a BLOCKING CI step since PR 7.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    env = dict(
        os.environ,
        # Held to the CPU backend (which makes bench.py trim its
        # iteration counts) and a tiny column count so the whole pass
        # is seconds, not hours.
        JAX_PLATFORMS="cpu",
        BENCH_COLUMNS=str(4 * (1 << 20)),  # 4 slices
        BENCH_SKIP_RESTART_PROBE="1",
        BENCH_SKIP_CLUSTER_TIER="1",
        BENCH_SKIP_HBM_TIER="1",
        # The open-loop storm tier has its own smoke (make load-smoke).
        BENCH_SKIP_ADMISSION_TIER="1",
        # The live-resize tier has its own smoke (make resize-smoke).
        BENCH_SKIP_REBALANCE_TIER="1",
        # The quorum-replication tier has its own smoke
        # (make replication-smoke).
        BENCH_SKIP_REPLICATION_TIER="1",
        # The composed-failure soak has its own smoke
        # (make gameday-smoke).
        BENCH_SKIP_GAMEDAY_TIER="1",
        # Mesh-scaling tier at smoke scale: tiny curve corpus, a
        # 16M-column headline (the 10B default is the real bench run),
        # light node-grid seeding.
        BENCH_MESH_SLICES="8",
        BENCH_MESH_COLUMNS=str(16 * (1 << 20)),
        BENCH_MESH_GRID_BITS="256",
        # Ingest tier at smoke scale: a shorter acked-write storm and
        # re-stage loop (still >= 100 rounds so the scatter-vs-
        # invalidate byte ratio assertion below stays meaningful).
        BENCH_INGEST_WRITES="80",
        BENCH_INGEST_READS="150",
        BENCH_INGEST_RESTAGE_ROUNDS="120",
        # Sparse tier at smoke scale: one slice per density corpus,
        # few timing reps — the assertions below are correctness/
        # wiring (byte identity, format mix, resident ratio), never
        # CPU timing.
        BENCH_SPARSE_SLICES="1",
        BENCH_SPARSE_ROWS="6",
        BENCH_SPARSE_REPS="3",
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env,
        capture_output=True,
        text=True,
        timeout=1200,
    )
    sys.stderr.write(proc.stderr[-4000:])
    if proc.returncode != 0:
        print(f"FAIL: bench.py exited {proc.returncode}", file=sys.stderr)
        return 1
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln.strip()]
    if not lines:
        print("FAIL: no stdout artifact", file=sys.stderr)
        return 1
    try:
        out = json.loads(lines[-1])
    except json.JSONDecodeError as e:
        print(f"FAIL: artifact is not JSON ({e}): {lines[-1]!r}", file=sys.stderr)
        return 1
    for key in ("metric", "value", "unit"):
        if key not in out:
            print(f"FAIL: artifact missing {key!r}", file=sys.stderr)
            return 1
    # Every result names the device it ran on, and a tier that raised
    # would have made bench.py exit 1 above.
    dev = out.get("device") or {}
    if dev.get("platform") != "cpu" or not dev.get("kind") or not dev.get("count"):
        print(f"FAIL: artifact does not name its device: {dev}", file=sys.stderr)
        return 1
    if out.get("failed_tiers") != []:
        print(f"FAIL: failed tiers: {out.get('failed_tiers')}", file=sys.stderr)
        return 1
    co = out.get("coalesce")
    if not isinstance(co, dict) or "total" not in co or "tiers" not in co:
        print(f"FAIL: artifact missing coalesce counters: {out}", file=sys.stderr)
        return 1
    total = co["total"]
    for key in ("launches", "queries", "mean_occupancy", "pad_rows"):
        if key not in total:
            print(f"FAIL: coalesce total missing {key!r}: {total}", file=sys.stderr)
            return 1
    if total["launches"] < 1 or total["queries"] < total["launches"]:
        print(f"FAIL: implausible coalesce counters: {total}", file=sys.stderr)
        return 1
    bsi = out.get("bsi")
    if not isinstance(bsi, dict):
        print(f"FAIL: artifact missing bsi tier: {out}", file=sys.stderr)
        return 1
    for section in ("range", "sum"):
        sec = bsi.get(section)
        if not isinstance(sec, dict):
            print(f"FAIL: bsi tier missing {section!r}: {bsi}", file=sys.stderr)
            return 1
        for key in ("gcols_s", "ms_per_query"):
            if not isinstance(sec.get(key), (int, float)) or sec[key] <= 0:
                print(
                    f"FAIL: bsi {section} missing/implausible {key!r}: {sec}",
                    file=sys.stderr,
                )
                return 1
    ms = out.get("mixed_storm")
    if not isinstance(ms, dict):
        print(f"FAIL: artifact missing mixed_storm tier: {out}", file=sys.stderr)
        return 1
    if ms.get("errors") != 0:
        print(f"FAIL: mixed_storm recorded errors: {ms}", file=sys.stderr)
        return 1
    for section in ("fusion_on", "fusion_off"):
        sec = ms.get(section)
        if not isinstance(sec, dict) or not sec:
            print(
                f"FAIL: mixed_storm missing {section!r}: {ms}", file=sys.stderr
            )
            return 1
    on_tiers = [
        v for v in ms["fusion_on"].values() if isinstance(v, dict)
    ]
    if not on_tiers or any(t.get("launches", 0) < 1 for t in on_tiers):
        print(
            f"FAIL: mixed_storm fusion-on launches implausible: {ms}",
            file=sys.stderr,
        )
        return 1
    # Fusion must actually engage on the mixed storm: interpreter
    # launches carrying >1 distinct-tree query each, and launches well
    # under the query count.
    total_fused = sum(t.get("fused_queries", 0) for t in on_tiers)
    total_q = sum(t.get("queries", 0) for t in on_tiers)
    total_launches = sum(t.get("launches", 0) for t in on_tiers)
    if total_fused < 1 or total_launches >= total_q:
        print(
            f"FAIL: mixed_storm fusion counters implausible"
            f" (fused={total_fused}, launches={total_launches},"
            f" queries={total_q}): {ms}",
            file=sys.stderr,
        )
        return 1
    for key in ("speedup", "interp_entries", "interp_entries_after_diversity"):
        if key not in ms:
            print(f"FAIL: mixed_storm missing {key!r}: {ms}", file=sys.stderr)
            return 1
    mesh = out.get("mesh_scaling")
    if not isinstance(mesh, dict):
        print(f"FAIL: artifact missing mesh_scaling tier: {out}", file=sys.stderr)
        return 1
    curve = mesh.get("curve")
    if not isinstance(curve, dict) or set(curve) != {"1", "2", "4", "8"}:
        print(
            f"FAIL: mesh_scaling curve must cover 1/2/4/8 devices: {mesh}",
            file=sys.stderr,
        )
        return 1
    for d, point in curve.items():
        if not point.get("byte_identical") or point.get("gcols_per_s", 0) <= 0:
            print(
                f"FAIL: mesh_scaling curve[{d}] implausible: {point}",
                file=sys.stderr,
            )
            return 1
        if point.get("sharded") != (d != "1"):
            print(
                f"FAIL: sharded execution must engage by default at"
                f" {d} devices: {point}",
                file=sys.stderr,
            )
            return 1
    hl = mesh.get("headline")
    if (
        not isinstance(hl, dict)
        or not hl.get("byte_identical")
        or hl.get("gcols_per_s", 0) <= 0
        or hl.get("devices", 0) < 2
    ):
        print(f"FAIL: mesh_scaling headline implausible: {hl}", file=sys.stderr)
        return 1
    ngrid = mesh.get("node_grid")
    if not isinstance(ngrid, dict) or not ngrid:
        print(f"FAIL: mesh_scaling missing node_grid: {mesh}", file=sys.stderr)
        return 1
    if not any(row.get("devices_per_node", 0) > 1 for row in ngrid.values()):
        print(
            f"FAIL: node_grid never ran a multi-device node: {ngrid}",
            file=sys.stderr,
        )
        return 1
    if not all(row.get("byte_identical") for row in ngrid.values()):
        print(f"FAIL: node_grid byte-check failed: {ngrid}", file=sys.stderr)
        return 1
    cold = out.get("cold_restart")
    if not isinstance(cold, dict):
        print(f"FAIL: artifact missing cold_restart tier: {out}", file=sys.stderr)
        return 1
    for key in ("first_answer_ms", "staging_complete_ms", "staging",
                "programs_compiled"):
        if key not in cold:
            print(f"FAIL: cold_restart missing {key!r}: {cold}", file=sys.stderr)
            return 1
    tiered = out.get("tiered")
    if not isinstance(tiered, dict):
        print(f"FAIL: artifact missing tiered tier: {out}", file=sys.stderr)
        return 1
    for section in ("unbounded", "tiered"):
        sec = tiered.get(section)
        if not isinstance(sec, dict) or any(
            k not in sec for k in ("p50_ms", "p99_ms")
        ):
            print(f"FAIL: tiered tier missing {section!r}: {tiered}", file=sys.stderr)
            return 1
    tt = tiered["tiered"]
    # The demotion/hydration cycle must actually run: a disk budget
    # << total bytes with zero demotions or hydrations means the cold
    # tier silently disengaged.
    if tt.get("demotions", 0) < 1 or tt.get("hydrations", 0) < 1:
        print(
            f"FAIL: tiered tier recorded no demotion/hydration cycle: {tt}",
            file=sys.stderr,
        )
        return 1
    if not (0 < tt.get("cold_hit_rate", 0) <= 1):
        print(f"FAIL: implausible cold-hit rate: {tt}", file=sys.stderr)
        return 1
    if tt.get("hydrate_p99_ms", 0) <= 0:
        print(f"FAIL: tiered tier missing hydration latency: {tt}", file=sys.stderr)
        return 1
    dg = out.get("degraded")
    if not isinstance(dg, dict):
        print(f"FAIL: artifact missing degraded tier: {out}", file=sys.stderr)
        return 1
    for section in ("healthy", "degraded"):
        sec = dg.get(section)
        if not isinstance(sec, dict) or sec.get("gcols_s", 0) <= 0 or (
            sec.get("p99_ms", 0) <= 0
        ):
            print(
                f"FAIL: degraded tier {section!r} implausible: {dg}",
                file=sys.stderr,
            )
            return 1
    if not dg.get("byte_identical"):
        print(
            f"FAIL: degraded tier host fallback not byte-identical: {dg}",
            file=sys.stderr,
        )
        return 1
    # The breaker must engage within its configured threshold (+ the
    # single transient retry), and the watchdog trip must recover in
    # bounded time — not the injected wedge's full duration.
    if dg.get("quarantine_queries", 99) > dg.get("quarantine_threshold", 0) + 1:
        print(f"FAIL: quarantine never engaged at threshold: {dg}", file=sys.stderr)
        return 1
    wd = dg.get("watchdog")
    if (
        not isinstance(wd, dict)
        or wd.get("trips", 0) < 1
        or not (0 < wd.get("trip_recovery_ms", 0) < wd.get("watchdog_ms", 0) * 4)
    ):
        print(f"FAIL: degraded tier watchdog implausible: {wd}", file=sys.stderr)
        return 1
    st = out.get("standing")
    if not isinstance(st, dict):
        print(f"FAIL: artifact missing standing tier: {out}", file=sys.stderr)
        return 1
    if st.get("subscriptions", 0) < 1000:
        print(
            f"FAIL: standing tier must run >= 1000 subscriptions: {st}",
            file=sys.stderr,
        )
        return 1
    lag = st.get("lag_ms")
    if (
        not isinstance(lag, dict)
        or lag.get("samples", 0) < 1
        or not isinstance(lag.get("p50"), (int, float))
        or not isinstance(lag.get("p99"), (int, float))
        or lag["p99"] <= 0
    ):
        print(f"FAIL: standing tier lag implausible: {st}", file=sys.stderr)
        return 1
    if st.get("updates", 0) < 1:
        print(f"FAIL: standing tier emitted no updates: {st}", file=sys.stderr)
        return 1
    qp = st.get("query_path")
    ratio = (qp or {}).get("p99_ratio")
    if not isinstance(qp, dict) or not isinstance(ratio, (int, float)):
        print(f"FAIL: standing tier missing query_path: {st}", file=sys.stderr)
        return 1
    # "Unchanged" with CI-runner headroom: the write-side listener
    # fan-out must not visibly tax the synchronous read path.
    if not (0 < ratio <= 3.0):
        print(
            f"FAIL: query-path p99 with subscriptions on is {ratio}x the"
            f" subscriptions-off baseline: {qp}",
            file=sys.stderr,
        )
        return 1
    ig = out.get("ingest")
    if not isinstance(ig, dict):
        print(f"FAIL: artifact missing ingest tier: {out}", file=sys.stderr)
        return 1
    gw = (ig.get("write") or {}).get("group_on")
    if not isinstance(gw, dict) or gw.get("acks", 0) < 1:
        print(f"FAIL: ingest tier group_on arm implausible: {ig}",
              file=sys.stderr)
        return 1
    # Group commit must actually batch: well under one fsync per acked
    # write (the whole point of the window), and the durable-write p99
    # bounded by the commit window — a per-ack-fsync regression shows
    # up as fsyncs ~= acks long before it shows up in latency.
    if gw.get("fsyncs", 0) < 1 or gw["fsyncs"] * 4 > gw["acks"]:
        print(
            f"FAIL: group commit not batching (fsyncs={gw.get('fsyncs')}"
            f" for {gw.get('acks')} acks): {gw}",
            file=sys.stderr,
        )
        return 1
    if not (0 < gw.get("write_p99_ms", 0) <= 50.0):
        print(f"FAIL: durable write p99 unbounded: {gw}", file=sys.stderr)
        return 1
    if (ig["write"].get("wal_off") or {}).get("fsyncs", -1) != 0:
        print(f"FAIL: wal_off arm fsynced: {ig['write']}", file=sys.stderr)
        return 1
    rd = ig.get("read")
    ig_ratio = (rd or {}).get("p99_ratio")
    if not isinstance(rd, dict) or not isinstance(ig_ratio, (int, float)):
        print(f"FAIL: ingest tier missing read arm: {ig}", file=sys.stderr)
        return 1
    # The WAL fsync wait must stay off the read path: read p99 under
    # the 50/50 storm within 1.5x of the control leg (the identical
    # writer storm against a disjoint frame, so in-process thread-
    # scheduling noise cancels and the ratio isolates what durable
    # ingest adds to the read tail).
    if not (0 < ig_ratio <= 1.5):
        print(
            f"FAIL: read p99 under 50/50 ingest storm is {ig_ratio}x"
            f" the control-storm baseline: {rd}",
            file=sys.stderr,
        )
        return 1
    rs = ig.get("restage")
    if (
        not isinstance(rs, dict)
        or (rs.get("scatter_off") or {}).get("restage_bytes", 0) <= 0
        or rs.get("bytes_ratio", 0) < 100
    ):
        print(
            f"FAIL: delta-scatter re-stage saving under 100x: {rs}",
            file=sys.stderr,
        )
        return 1
    if (rs.get("scatter") or {}).get("launches", 0) < 1:
        print(f"FAIL: scatter arm never launched: {rs}", file=sys.stderr)
        return 1
    # Sparse tier (ISSUE 19): compressed device planes.  Every density
    # corpus must report byte-identical results between the auto and
    # forced-dense arms; the low-density corpora must actually pick
    # compressed container formats; and the 1% corpus's resident HBM
    # must sit >= 10x below its logical dense geometry.
    sp = out.get("sparse")
    if not isinstance(sp, dict) or not isinstance(sp.get("densities"), dict):
        print(f"FAIL: artifact missing sparse tier: {out}", file=sys.stderr)
        return 1
    spd = sp["densities"]
    for tag in ("50", "5", "1", "0.1"):
        ent = spd.get(tag)
        if not isinstance(ent, dict):
            print(f"FAIL: sparse tier missing density {tag}: {spd}",
                  file=sys.stderr)
            return 1
        if ent.get("byte_identical") is not True:
            print(
                f"FAIL: sparse {tag}% storm diverged from the dense arm:"
                f" {ent}",
                file=sys.stderr,
            )
            return 1
        if ent.get("storm_queries", 0) < 1:
            print(f"FAIL: sparse {tag}% storm ran no queries: {ent}",
                  file=sys.stderr)
            return 1
    d1 = spd["1"]
    mix1 = d1.get("format_mix", {})
    if mix1.get("rle", 0) < 1 or mix1.get("sparse", 0) < 1:
        print(
            f"FAIL: 1% corpus picked no compressed formats: {mix1}",
            file=sys.stderr,
        )
        return 1
    if d1.get("resident_ratio", 0) < 10:
        print(
            f"FAIL: 1% resident HBM under 10x below logical: {d1}",
            file=sys.stderr,
        )
        return 1
    if d1.get("bytes_read", 0) <= 0 or d1.get("logical_bytes", 0) <= d1.get(
        "bytes_read", 0
    ):
        print(
            f"FAIL: 1% effective bytes not below logical: {d1}",
            file=sys.stderr,
        )
        return 1
    pc = out.get("program_cache")
    if not isinstance(pc, dict) or "entries" not in pc or "bounds" not in pc:
        print(f"FAIL: artifact missing program_cache: {out}", file=sys.stderr)
        return 1
    for fam, bound in pc["bounds"].items():
        if pc["entries"].get(fam, 0) > bound:
            print(
                f"FAIL: program cache family {fam!r} exceeds its hard"
                f" bound: {pc}",
                file=sys.stderr,
            )
            return 1
    # Launch telemetry (obs/perf.py): the artifact's perf block must
    # carry per-site roofline figures — the bench drives the coalescer
    # hard, so at minimum the coalesce site recorded launches with a
    # positive achieved GB/s, and every reported site is self-
    # consistent (launches >= 1, gbps > 0 whenever bytes moved).
    perf = out.get("perf")
    if not isinstance(perf, dict) or not isinstance(perf.get("sites"), dict):
        print(f"FAIL: artifact missing perf block: {out}", file=sys.stderr)
        return 1
    sites = perf["sites"]
    if not sites:
        print("FAIL: perf block recorded no launch sites", file=sys.stderr)
        return 1
    for name, site in sites.items():
        if site.get("launches", 0) < 1:
            print(f"FAIL: perf site {name!r} implausible: {site}", file=sys.stderr)
            return 1
    if "coalesce" not in sites or sites["coalesce"].get("gbps", 0) <= 0:
        print(
            f"FAIL: perf block missing coalesce-site bandwidth: {sites}",
            file=sys.stderr,
        )
        return 1
    if not isinstance(perf.get("compile_ms"), dict):
        print(f"FAIL: perf block missing compile_ms: {perf}", file=sys.stderr)
        return 1
    # The native histogram families must render as valid Prometheus
    # exposition (in-process — the smoke already booted servers above;
    # this checks the renderer directly so a grammar regression fails
    # here, not in a user's scraper).
    sys.path.insert(0, REPO)
    from pilosa_tpu.obs import perf as perf_mod

    lh = perf_mod.LatencyHistograms(slo_ms=50.0)
    lh.observe_query("point", 12.0)
    lh.observe_http("GET", "/index/{index}/query", 3.0)
    text = lh.render()
    types = [ln for ln in text.splitlines() if ln.startswith("# TYPE")]
    fams = [ln.split()[2] for ln in types]
    if len(fams) != len(set(fams)):
        print(f"FAIL: duplicate # TYPE lines in histogram render: {fams}",
              file=sys.stderr)
        return 1
    for fam in ("pilosa_query_latency_ms", "pilosa_http_latency_ms"):
        if fam not in fams or f"{fam}_bucket{{" not in text:
            print(f"FAIL: histogram family {fam} missing: {fams}",
                  file=sys.stderr)
            return 1
        if f"{fam}_count" not in text or f"{fam}_sum" not in text:
            print(f"FAIL: {fam} missing _count/_sum", file=sys.stderr)
            return 1
        if 'le="+Inf"' not in text:
            print("FAIL: histogram missing +Inf bucket", file=sys.stderr)
            return 1
    print(
        f"OK: metric={out['metric']} value={out['value']} {out['unit']};"
        f" coalesce launches={total['launches']}"
        f" queries={total['queries']}"
        f" mean_occupancy={total['mean_occupancy']};"
        f" bsi range {bsi['range']['gcols_s']} Gcols/s"
        f" / sum {bsi['sum']['gcols_s']} Gcols/s;"
        f" mixed_storm fused={total_fused}/{total_q} queries over"
        f" {total_launches} launches, speedup={ms['speedup']},"
        f" interp entries {ms['interp_entries']}->"
        f"{ms['interp_entries_after_diversity']};"
        f" mesh curve {[curve[d]['gcols_per_s'] for d in ('1', '2', '4', '8')]}"
        f" Gcols/s, headline {hl['columns']} cols @ {hl['devices']} dev"
        f" = {hl['gcols_per_s']} Gcols/s, grid {sorted(ngrid)};"
        f" cold restart first answer {cold['first_answer_ms']} ms;"
        f" tiered p99 {tt['p99_ms']} ms ({tt['demotions']} demotions,"
        f" {tt['hydrations']} hydrations, cold-hit {tt['cold_hit_rate']});"
        f" degraded {dg['degraded']['gcols_s']} vs healthy"
        f" {dg['healthy']['gcols_s']} Gcols/s, watchdog recovery"
        f" {dg['watchdog']['trip_recovery_ms']} ms;"
        f" standing {st['subscriptions']} subs, lag p99 {lag['p99']} ms,"
        f" query-path p99 ratio {ratio}x;"
        f" ingest {gw['acks_per_s']} acks/s ({gw['fsyncs']} fsyncs /"
        f" {gw['acks']} acks), 50/50 read p99 {ig_ratio}x, re-stage"
        f" saving {rs['bytes_ratio']}x;"
        f" sparse 1% mix {d1['format_mix']}, resident"
        f" {d1.get('resident_ratio')}x below logical, byte-identical;"
        f" perf sites {sorted(sites)} (coalesce"
        f" {sites['coalesce']['gbps']} GB/s)"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
