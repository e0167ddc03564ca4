"""Benchmark: 1B-column PQL Intersect+Count throughput (BASELINE.json
north_star / configs[3]-shaped workload).

Measures three tiers on the accelerator, logging all to stderr:

1. RAW KERNEL — the fused AND+popcount program over a pre-staged
   [954, 2, 32768] device batch (the compute ceiling).  Distinct input
   batches are cycled so no result cache can fake the number.
2. END-TO-END EXECUTOR — the same query as PQL text through
   ``Executor.execute`` against a real Holder with 954 fragments:
   parsing, leaf resolution, batch assembly/caching, reduce
   (reference path: handlePostQuery -> mapReduce,
   executor.go:1246-1282).  BASELINE's north-star metric is THIS.
3. TopN — the real executor path over ranked-cache candidates
   (reference: fragment.go:505-639, executor.go:281-321; all-local
   queries take the folded single-device-fetch protocol, which returns
   results identical to the reference's two-phase refetch).

BANDWIDTH ACCOUNTING: the fused Intersect+Count reads two operands of
total_columns/8 bytes each and writes nothing that leaves the chip, so
effective bytes/query = total_columns/4.  Every Gcols/s figure is
accompanied by effective GB/s and % of HBM peak (v5e ~819 GB/s) so the
distance to the memory-bound ceiling is visible in the artifacts.

THROUGHPUT vs LATENCY: the executor tiers report (a) single-query
synchronous p50 latency and (b) per-query time under CONCURRENT load
(a thread pool issuing many queries at once — how the reference's
HTTP server runs, one goroutine per request).  The headline is the
concurrent throughput: BASELINE's north star is "rows/sec".  Both
numbers go to stderr.

The host-CPU numpy ``bitwise_count`` pass stands in for the reference's
Go/amd64 POPCNT roaring loop (reference: roaring/assembly_amd64.s);
goal >=10x (BASELINE.md).

The bench runs on whatever backend JAX gives it and never switches: the
result names ``platform``, ``device_kind`` and the device count, so a
run held to the CPU (``JAX_PLATFORMS=cpu``, which also trims iteration
counts — a wiring check, see tools/bench_smoke.py) cannot be read as a
device number.  A tier that raises is logged, listed under
``failed_tiers`` and makes the run exit 1.

Prints exactly ONE JSON line on stdout:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}
(the end-to-end executor throughput — the honest number).
Progress goes to stderr.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

import numpy as np


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def measure_slope(fold, lo_in, hi_in, bytes_per, sanity_peak, log_fn,
                  epochs: int = 6, tries: int = 3) -> float | None:
    """Interleaved lo/hi fetch-folded slope with HBM-peak plausibility
    retry.  ``fold(inputs) -> wall seconds`` must force execution (fold
    outputs into one fetched scalar); lo/hi epochs interleave so both
    see the same conditions; a slope implying more operand bandwidth
    than the chip's HBM peak is retried and ultimately reported as None
    rather than published."""
    n = len(hi_in) - len(lo_in)
    for attempt in range(tries):
        lo = hi = float("inf")
        for _ in range(epochs):
            lo = min(lo, fold(lo_in))
            hi = min(hi, fold(hi_in))
        s = (hi - lo) / n
        if s > 0 and (sanity_peak is None or bytes_per / s <= sanity_peak):
            return s
        log_fn(
            f"slope measurement implausible (slope {s*1e6:.1f} us/run);"
            f" interference — retry {attempt + 1}/{tries}"
        )
    return None


def hbm_peak_bytes_s(jax_mod) -> float | None:
    """Per-generation HBM peak for the %-of-peak roofline figure; None
    (omit the percentage) for unrecognized device kinds rather than
    reporting against the wrong ceiling."""
    kind = jax_mod.devices()[0].device_kind.lower()
    for pat, peak in (
        ("v5 lite", 819e9), ("v5e", 819e9), ("v5litepod", 819e9),
        ("v6 lite", 1640e9), ("v6e", 1640e9),
        ("v5p", 2765e9), ("v5", 2765e9),
        ("v4", 1228e9), ("v3", 900e9), ("v2", 700e9),
    ):
        if pat in kind:
            return peak
    log(f"unknown TPU device kind {kind!r}: omitting %-of-HBM-peak")
    return None


def prime_fragment(frag, rows: np.ndarray, pad_rows_fn) -> None:
    """Plane-inject ``rows`` (uint32[n, words]) into a fragment and
    prime its caches — shared by every bench tier (the import path is
    not what the bench measures)."""
    n = rows.shape[0]
    plane = np.zeros((pad_rows_fn(n), rows.shape[1]), np.uint32)
    plane[:n] = rows
    counts = np.bitwise_count(rows).sum(axis=-1, dtype=np.int64)
    frag._plane = plane
    frag._slot_of = {r: r for r in range(n)}
    frag._count_of = {r: int(counts[r]) for r in range(n)}
    frag._max_row_id = n - 1
    frag._version += 1
    for r in range(n):
        frag.cache.bulk_add(r, int(counts[r]))
    frag.cache.invalidate()


def build_holder(leaves: np.ndarray, data_dir: str):
    """A real Holder with one fragment per slice holding rows {1, 2}
    from ``leaves`` (uint32[n_slices, 2, words]) — plane-injected (the
    import path is not what this bench measures)."""
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.ops import bitplane as bp

    holder = Holder(data_dir)
    holder.open()
    idx = holder.create_index("i")
    f = idx.create_frame("f")
    view = f.create_view_if_not_exists("standard")
    # Rows 1 and 2 occupy slots 0 and 1 (shifted ids, so prime_fragment
    # does not fit); plane-inject directly.
    counts = np.bitwise_count(leaves).sum(axis=-1, dtype=np.int64)
    for s in range(leaves.shape[0]):
        frag = view.create_fragment_if_not_exists(s)
        plane = np.zeros((bp.pad_rows(2), leaves.shape[2]), np.uint32)
        plane[:2] = leaves[s]
        frag._plane = plane
        frag._slot_of = {1: 0, 2: 1}
        frag._count_of = {1: int(counts[s, 0]), 2: int(counts[s, 1])}
        frag._max_row_id = 2
        frag._version += 1
    return holder


# Tiers that raised (or whose subprocess failed): the run exits 1.
FAILED_TIERS: list[str] = []


def tier_failed(label: str, why: str) -> None:
    log(f"{label} tier FAILED: {why}")
    FAILED_TIERS.append(label)


def main() -> None:
    import jax
    import jax.numpy as jnp

    from pilosa_tpu.exec import plan, warmup
    from pilosa_tpu.exec.executor import Executor
    from pilosa_tpu.ops.bitplane import SLICE_WIDTH, WORDS_PER_SLICE
    from pilosa_tpu.pql.parser import parse_string

    # A run held to the CPU is a wiring check (tools/bench_smoke.py):
    # iteration counts are trimmed, and the result says "platform": "cpu".
    # Read from the environment: the children below need the backend
    # before this process may take it.
    cpu_fallback = os.environ.get("JAX_PLATFORMS") == "cpu"

    # Persistent XLA compile cache (exec/warmup.py): restarts
    # deserialize the fused programs from disk instead of recompiling.
    # JAX_COMPILATION_CACHE_DIR when set, else one fixed path in the
    # checkout.
    cache_dir = warmup.resolve_cache_dir()
    had_cache = os.path.isdir(cache_dir) and bool(os.listdir(cache_dir))
    warmup.enable_compile_cache()
    log(f"compile cache: {cache_dir} ({'warm' if had_cache else 'cold'})")
    # Restart probes (fresh subprocesses, sequential — never concurrent
    # with this process's device use): first run populates the disk
    # cache (true cold compile), second measures a process restart
    # loading it.  Run BEFORE this process touches the backend: a chip
    # belongs to one process at a time.
    restart_probe: dict = {}
    if os.environ.get("BENCH_SKIP_RESTART_PROBE") != "1":
        import subprocess

        probe = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "tools",
            "compile_probe_restart.py",
        )
        for label in ("cold" if not had_cache else "warm-disk", "restart"):
            try:
                out = subprocess.run(
                    [sys.executable, probe, cache_dir],
                    capture_output=True,
                    timeout=600,
                    text=True,
                )
                if out.returncode != 0 or not out.stdout.strip():
                    tier_failed(
                        "restart-probe",
                        f"({label}) rc={out.returncode} "
                        f"stderr={out.stderr.strip()[-300:]!r}",
                    )
                    break
                t = float(out.stdout.strip().splitlines()[-1])
                restart_probe[label.replace("-", "_") + "_compile_s"] = round(
                    t, 3
                )
                log(f"headline-program compile, fresh process ({label}): "
                    f"{t*1e3:.0f} ms")
            except Exception as e:
                tier_failed("restart-probe", f"({label}) {e}")
                break

    # Cluster-reduce tier (BASELINE configs[4] shape): runs in a CPU
    # subprocess BEFORE this process touches the device — coordinator
    # fan-out/reduce overhead is host-side.  ~1 min.
    cluster_reduce = None
    if os.environ.get("BENCH_SKIP_CLUSTER_TIER") != "1":
        import subprocess

        cb = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "tools", "cluster_bench.py"
        )
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        try:
            out = subprocess.run(
                [sys.executable, cb], env=env, capture_output=True,
                timeout=900, text=True,
            )
            if out.returncode == 0 and out.stdout.strip():
                for line in out.stderr.strip().splitlines():
                    log(line)
                last = out.stdout.strip().splitlines()[-1]
                log(f"cluster_reduce tier: {last}")
                try:
                    cluster_reduce = json.loads(last)
                except json.JSONDecodeError:
                    pass
            else:
                tier_failed(
                    "cluster",
                    f"rc={out.returncode} "
                    f"stderr={out.stderr.strip()[-300:]!r}",
                    )
        except Exception as e:
            tier_failed("cluster", str(e))

    # Admission-storm tier: the open-loop sustained-load harness
    # (tools/load_harness.py) self-boots a node twice — admission ON
    # then OFF — and sweeps offered load past 2-3x capacity, recording
    # goodput-vs-offered-load and the max-sustained-QPS-at-p99-SLO
    # figure.  A CPU subprocess like the cluster tier: admission and
    # the HTTP/queue path under storm are host-side, and the open-loop
    # generator must not contend with this process's device work.
    admission_storm = None
    if os.environ.get("BENCH_SKIP_ADMISSION_TIER") != "1":
        import subprocess

        lh = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "tools", "load_harness.py"
        )
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        try:
            out = subprocess.run(
                [sys.executable, lh, "--self-boot", "--compare",
                 "--slices", "8", "--duration", "5", "--deadline-ms", "500",
                 "--slo-ms", "250",
                 # Gates sized to the CPU node this tier boots (see
                 # docs/administration.md "Sizing the gates"): C/S*1000
                 # against single-digit-ms service times.  The config
                 # defaults are sized for TPU-class nodes and would
                 # over-admit here.
                 "--point-concurrency", "4", "--heavy-concurrency", "2",
                 "--write-concurrency", "2", "--queue-depth", "4"],
                env=env, capture_output=True, timeout=900, text=True,
            )
            if out.returncode == 0 and out.stdout.strip():
                for line in out.stderr.strip().splitlines():
                    log(line)
                admission_storm = json.loads(
                    out.stdout.strip().splitlines()[-1]
                )
                log(
                    "admission_storm tier: max sustained "
                    f"{admission_storm['max_sustained_qps_at_p99_slo']} qps "
                    "at p99 SLO"
                )
            else:
                tier_failed(
                    "admission",
                    f"rc={out.returncode} "
                    f"stderr={out.stderr.strip()[-300:]!r}",
                    )
        except Exception as e:
            tier_failed("admission", str(e))

    # Rebalance tier: live 2->3 grow under sustained load, one process
    # per node (tools/rebalance_bench.py) — read p50/p99 during the
    # background slice migration vs steady state, migration seconds,
    # zero-lost-writes and byte-identical-results checks.  Host-side
    # like the other cluster tiers; runs before this process touches
    # the device.
    rebalance_tier = None
    if os.environ.get("BENCH_SKIP_REBALANCE_TIER") != "1":
        import subprocess

        rbt = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "tools",
            "rebalance_bench.py",
        )
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        try:
            out = subprocess.run(
                [sys.executable, rbt], env=env, capture_output=True,
                timeout=900, text=True,
            )
            if out.returncode == 0 and out.stdout.strip():
                for line in out.stderr.strip().splitlines():
                    log(line)
                rebalance_tier = json.loads(out.stdout.strip().splitlines()[-1])
                log(
                    "rebalance tier: migration "
                    f"{rebalance_tier['migration_s']}s, read p99 "
                    f"{rebalance_tier['p99_ratio']}x steady, "
                    f"{rebalance_tier['writes_lost']} writes lost"
                )
            else:
                tier_failed(
                    "rebalance",
                    f"rc={out.returncode} "
                    f"stderr={out.stderr.strip()[-300:]!r}",
                    )
        except Exception as e:
            tier_failed("rebalance", str(e))

    # Replication tier (ISSUE 14 / ROADMAP 4): quorum write latency at
    # one/quorum/all over a 3-node replica-3 cluster, plus the hinted-
    # handoff drain rate — kill a replica under a quorum write burst,
    # restart it, time breaker-triggered replay to checksum
    # convergence (tools/replication_bench.py subprocess, CPU).
    replication_tier = None
    if os.environ.get("BENCH_SKIP_REPLICATION_TIER") != "1":
        import subprocess

        rpt = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "tools",
            "replication_bench.py",
        )
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        try:
            out = subprocess.run(
                [sys.executable, rpt], env=env, capture_output=True,
                timeout=900, text=True,
            )
            if out.returncode == 0 and out.stdout.strip():
                for line in out.stderr.strip().splitlines():
                    if line.startswith("[replication]"):
                        log(line)
                replication_tier = json.loads(
                    out.stdout.strip().splitlines()[-1]
                )
                hr = replication_tier.get("hint_replay", {})
                log(
                    "replication tier: quorum write p99 "
                    f"{replication_tier['writes']['quorum']['p99_ms']} ms, "
                    f"hint drain {hr.get('hints_per_s')}/s "
                    f"(converged={hr.get('converged')})"
                )
            else:
                tier_failed(
                    "replication",
                    f"rc={out.returncode} "
                    f"stderr={out.stderr.strip()[-300:]!r}",
                    )
        except Exception as e:
            tier_failed("replication", str(e))

    # Degraded tier (ISSUE 15): device-fault tolerance figures —
    # healthy vs quarantined-host-fallback Count Gcols/s + p50/p99
    # (every degraded answer byte-checked), queries-to-quarantine at
    # the configured threshold, and the watchdog trip recovery time
    # for a hang injected inside the collective dispatch
    # (tools/degraded_bench.py subprocess on the virtual mesh, CPU).
    degraded_tier = None
    if os.environ.get("BENCH_SKIP_DEGRADED_TIER") != "1":
        import subprocess

        dgt = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "tools",
            "degraded_bench.py",
        )
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        try:
            out = subprocess.run(
                [sys.executable, dgt], env=env, capture_output=True,
                timeout=900, text=True,
            )
            if out.returncode == 0 and out.stdout.strip():
                for line in out.stderr.strip().splitlines():
                    if line.startswith("[degraded]"):
                        log(line)
                degraded_tier = json.loads(out.stdout.strip().splitlines()[-1])
                log(
                    "degraded tier: healthy "
                    f"{degraded_tier['healthy']['gcols_s']} Gcols/s vs "
                    f"host-fallback {degraded_tier['degraded']['gcols_s']} "
                    f"Gcols/s; watchdog trip recovery "
                    f"{degraded_tier['watchdog']['trip_recovery_ms']} ms"
                )
            else:
                tier_failed(
                    "degraded",
                    f"rc={out.returncode} "
                    f"stderr={out.stderr.strip()[-300:]!r}",
                    )
        except Exception as e:
            tier_failed("degraded", str(e))

    # Standing-query tier (ISSUE 16): N >= 1000 push-based PQL
    # subscriptions under a live import stream — registration ms/sub,
    # update-lag p50/p99, delta-eval tier counts, and the query-path
    # p99 with subscriptions on vs the identical node with them off
    # (tools/standing_bench.py subprocess, CPU: the subscribe engine is
    # host-side — listener fan-out, coalescing, incremental eval).
    standing_tier = None
    if os.environ.get("BENCH_SKIP_STANDING_TIER") != "1":
        import subprocess

        sbt = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "tools",
            "standing_bench.py",
        )
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        try:
            out = subprocess.run(
                [sys.executable, sbt], env=env, capture_output=True,
                timeout=900, text=True,
            )
            if out.returncode == 0 and out.stdout.strip():
                for line in out.stderr.strip().splitlines():
                    if line.startswith("[standing]"):
                        log(line)
                standing_tier = json.loads(out.stdout.strip().splitlines()[-1])
                log(
                    "standing tier: "
                    f"{standing_tier['subscriptions']} subscriptions, "
                    f"update lag p99 {standing_tier['lag_ms']['p99']} ms, "
                    "query-path p99 ratio "
                    f"{standing_tier['query_path']['p99_ratio']}x off"
                )
            else:
                tier_failed(
                    "standing",
                    f"rc={out.returncode} "
                    f"stderr={out.stderr.strip()[-300:]!r}",
                    )
        except Exception as e:
            tier_failed("standing", str(e))

    # Ingest tier (ISSUE 18): what durability costs and what delta-
    # scatter saves — acked write throughput with group commit on/off
    # vs the WAL-off baseline (fsyncs vs acks), read p99 under a 50/50
    # read/write storm vs read-only, and mirror re-stage bytes with
    # scatter on/off (tools/ingest_bench.py subprocess, CPU).
    ingest_tier = None
    if os.environ.get("BENCH_SKIP_INGEST_TIER") != "1":
        import subprocess

        igt = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "tools",
            "ingest_bench.py",
        )
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        try:
            out = subprocess.run(
                [sys.executable, igt], env=env, capture_output=True,
                timeout=900, text=True,
            )
            if out.returncode == 0 and out.stdout.strip():
                for line in out.stderr.strip().splitlines():
                    if line.startswith("[ingest]"):
                        log(line)
                ingest_tier = json.loads(out.stdout.strip().splitlines()[-1])
                gw = ingest_tier["write"]["group_on"]
                log(
                    f"ingest tier: {gw['acks_per_s']} durable acks/s "
                    f"({gw['fsyncs']} fsyncs / {gw['acks']} acks), "
                    f"50/50 read p99 {ingest_tier['read']['p99_ratio']}x "
                    "the control storm, re-stage bytes "
                    f"{ingest_tier['restage']['bytes_ratio']}x saved by "
                    "scatter"
                )
            else:
                tier_failed(
                    "ingest",
                    f"rc={out.returncode} "
                    f"stderr={out.stderr.strip()[-300:]!r}",
                    )
        except Exception as e:
            tier_failed("ingest", str(e))

    # Sparse tier (ISSUE 19): compressed device planes — effective
    # Gcols/s, device bytes read vs logical geometry, container-format
    # mix, and compressed-vs-logical resident HBM over 50%/5%/1%/0.1%
    # density corpora, with a byte-identity PQL storm against the
    # forced-dense arm (tools/sparse_bench.py subprocess, CPU).
    sparse_tier = None
    if os.environ.get("BENCH_SKIP_SPARSE_TIER") != "1":
        import subprocess

        spt = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "tools",
            "sparse_bench.py",
        )
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        try:
            out = subprocess.run(
                [sys.executable, spt], env=env, capture_output=True,
                timeout=900, text=True,
            )
            if out.returncode == 0 and out.stdout.strip():
                for line in out.stderr.strip().splitlines():
                    if line.startswith("[sparse]"):
                        log(line)
                sparse_tier = json.loads(out.stdout.strip().splitlines()[-1])
                d1 = sparse_tier["densities"]["1"]
                log(
                    "sparse tier: 1% density "
                    f"{d1['effective_gcols_s']} Gcols/s effective "
                    f"({d1['speedup']}x dense arm), resident HBM "
                    f"{d1.get('resident_ratio', 0)}x below logical, "
                    f"mix {d1['format_mix']}"
                )
            else:
                tier_failed(
                    "sparse",
                    f"rc={out.returncode} "
                    f"stderr={out.stderr.strip()[-300:]!r}",
                    )
        except Exception as e:
            tier_failed("sparse", str(e))

    # Gameday tier: the everything-at-once soak (tools/gameday.py) at
    # full scale — multi-tenant fairness under a quota-shedding storm,
    # kill -9 replica recovery with zero lost acked writes, resize
    # 2->3->2 under a windowed device-fault timeline with tier
    # demote/hydrate and subscription convergence, gossip under
    # datagram loss.  A CPU subprocess like the cluster tiers (it
    # re-execs onto its own virtual 8-device mesh); the per-leg
    # numbers (victim p99 ratio, recovery counters, sub lag) land in
    # the artifact as the composed-failure resilience record.
    gameday_tier = None
    if os.environ.get("BENCH_SKIP_GAMEDAY_TIER") != "1":
        import subprocess

        gd = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "tools",
            "gameday.py",
        )
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        try:
            out = subprocess.run(
                [sys.executable, gd], env=env, capture_output=True,
                timeout=900, text=True,
            )
            if out.returncode == 0 and out.stdout.strip():
                for line in out.stderr.strip().splitlines():
                    if line.startswith("[gameday"):
                        log(line)
                gameday_tier = json.loads(out.stdout.strip().splitlines()[-1])
                fair = gameday_tier["legs"]["fairness"]
                log(
                    "gameday tier: all legs green — victim p99 "
                    f"{fair['victim_p99_storm_ms']} ms under storm "
                    f"({fair['ratio']}x isolated), hot shed "
                    f"{fair['hot_shed']}, wall {gameday_tier['wall_s']} s"
                )
            else:
                tier_failed(
                    "gameday",
                    f"rc={out.returncode} "
                    f"stderr={out.stderr.strip()[-300:]!r}",
                    )
        except Exception as e:
            tier_failed("gameday", str(e))

    # Mesh-scaling tier (ISSUE 12 / ROADMAP 2): the mesh-sharded data
    # plane end to end — devices-vs-Gcols/s curve at 1/2/4/8 devices,
    # the 10B-column Intersect+Count headline over the full mesh (ICI-
    # reduced limb total-count), and the N-nodes × M-devices grid with
    # one process per node.  Runs on the virtual 8-device CPU mesh
    # (tools/mesh_bench.py re-execs itself onto it) BEFORE this process
    # touches the device.
    mesh_scaling = None
    if os.environ.get("BENCH_SKIP_MESH_TIER") != "1":
        import subprocess

        mb = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "tools", "mesh_bench.py"
        )
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        try:
            out = subprocess.run(
                [sys.executable, mb], env=env, capture_output=True,
                timeout=1800, text=True,
            )
            if out.returncode == 0 and out.stdout.strip():
                for line in out.stderr.strip().splitlines():
                    if line.startswith("[mesh]"):
                        log(line)
                mesh_scaling = json.loads(out.stdout.strip().splitlines()[-1])
                hl = mesh_scaling.get("headline") or {}
                log(
                    "mesh_scaling tier: headline "
                    f"{hl.get('columns')} columns @ {hl.get('devices')} "
                    f"devices -> {hl.get('gcols_per_s')} Gcols/s, "
                    f"byte_identical={hl.get('byte_identical')}"
                )
            else:
                tier_failed(
                    "mesh",
                    f"rc={out.returncode} "
                    f"stderr={out.stderr.strip()[-300:]!r}",
                    )
        except Exception as e:
            tier_failed("mesh", str(e))

    total_columns = int(os.environ.get("BENCH_COLUMNS", 1_000_000_000))
    n_slices = (total_columns + SLICE_WIDTH - 1) // SLICE_WIDTH  # 954
    log(f"backend={jax.default_backend()} devices={jax.devices()}")
    log(f"building {n_slices} slices x 2 rows x {WORDS_PER_SLICE} words (~50% density)")

    rng = np.random.default_rng(7)
    leaves = rng.integers(
        0, 2**32, size=(n_slices, 2, WORDS_PER_SLICE), dtype=np.uint32
    )

    # --- host-CPU baseline: the reference's popcntAndSlice loop shape ---
    a, b = leaves[:, 0], leaves[:, 1]
    t0 = time.perf_counter()
    host_count = int(np.bitwise_count(a & b).sum())
    host_s = time.perf_counter() - t0
    log(f"host AND+popcount: {host_s:.3f}s -> {host_count}")

    # --- device: fused Intersect+Count, batched over all slices ---
    q = parse_string("Count(Intersect(Bitmap(rowID=1), Bitmap(rowID=2)))")
    expr, _ = plan.decompose(q.calls[0].children[0])

    # Distinct batches, cycled: defeats any (executable, args) result
    # caching between the client and the chip.  Batch 0 is `leaves`
    # (the bit-exactness anchor).  The slice axis pads (zero slices) to
    # a multiple of 8 — zero slices contribute nothing to the counts,
    # and every timed program sees the identical padded shape.
    n_pad = (n_slices + 7) // 8 * 8

    def staged(arr: np.ndarray):
        if n_pad != arr.shape[0]:
            arr = np.concatenate(
                [arr, np.zeros((n_pad - arr.shape[0],) + arr.shape[1:], arr.dtype)]
            )
        return jnp.asarray(arr)

    n_batches = 3
    devs = [staged(leaves)]
    host_counts = [host_count]
    for _ in range(n_batches - 1):
        extra = rng.integers(
            0, 2**32, size=(n_slices, 2, WORDS_PER_SLICE), dtype=np.uint32
        )
        host_counts.append(int(np.bitwise_count(extra[:, 0] & extra[:, 1]).sum()))
        devs.append(staged(extra))
    jax.block_until_ready(devs)

    # TIMING METHODOLOGY: a measurement FOLDS N executions' outputs
    # into one device scalar and fetches it (all N must really finish),
    # and the per-run time is the SLOPE between a 28-run and a 4-run
    # folded pass — the fixed dispatch + fetch cost cancels.  Cycling 3
    # distinct staged batches defeats any (executable, args) result
    # cache.  A run held to the CPU trims iteration counts: the same
    # slope scheme at 1B columns takes >1h on the host backend, and its
    # result is a wiring record, not a device number.
    N_LO, N_HI = (2, 6) if cpu_fallback else (4, 28)
    SLOPE_EPOCHS = 2 if cpu_fallback else 6

    def folded_wall(fn, inputs) -> float:
        acc = None
        t0 = time.perf_counter()
        for d in inputs:
            part = fn(d).astype(jnp.float32).sum()
            acc = part if acc is None else acc + part
        float(np.asarray(acc))
        return time.perf_counter() - t0

    sanity_peak = hbm_peak_bytes_s(jax) if jax.default_backend() == "tpu" else None

    def slope_time(fn) -> float | None:
        """True per-execution device seconds for ``fn`` (see
        measure_slope for the methodology)."""
        return measure_slope(
            lambda inputs: folded_wall(fn, inputs),
            [devs[i % n_batches] for i in range(N_LO)],
            [devs[i % n_batches] for i in range(N_HI)],
            devs[0].size * 4,
            sanity_peak * 1.25 if sanity_peak else None,
            log,
            epochs=SLOPE_EPOCHS,
        )

    def time_variant(name: str, fn) -> float | None:
        for d, want in zip(devs, host_counts):  # warmup/compile + exactness
            got = int(np.asarray(jax.block_until_ready(fn(d)), dtype=np.int64).sum())
            assert got == want, f"bit-exactness ({name}): {got} != {want}"
        s = slope_time(fn)
        if s is None:
            log(f"device {name} Intersect+Count: slope UNRELIABLE (interference)")
        else:
            log(
                f"device {name} Intersect+Count: {s*1e3:.2f} ms/query"
                f" (fold-fetched slope, best of {SLOPE_EPOCHS} epochs)"
            )
        return s

    # --- roofline decomposition (stderr evidence for the bandwidth
    # analysis): a pure streaming reduce (1 vector op/word — the
    # practical memory-bound ceiling for this access pattern), popcount
    # +reduce (~12 bit-hack ops/word on the VPU — TPUs have no popcount
    # unit), and the production fused AND+popcount+reduce.  If popcount
    # tracks fused and both sit far below the streaming ceiling, the
    # kernel is VPU-popcount-bound, not HBM-bound, and %-of-HBM-peak is
    # the wrong roofline for it.
    def probe(name, fn):
        if cpu_fallback:
            return None  # TPU evidence only; hour-scale on the host
        try:
            f = jax.jit(fn)
            jax.block_until_ready(f(devs[0]))  # compile
            s = slope_time(f)
            if s is None:
                log(f"roofline {name}: UNRELIABLE (interference)")
                return None
            gbs = (devs[0].size * 4) / s / 1e9
            log(f"roofline {name}: {s*1e3:.2f} ms/pass ({gbs:.0f} GB/s read)")
            return s
        except Exception as e:  # noqa: BLE001 — probes are evidence only
            log(f"roofline {name} failed: {e!r:.200}")
            return None

    stream_s = probe("stream-sum", lambda d: jnp.sum(d, dtype=jnp.uint32))
    probe(
        "popcount-sum",
        lambda d: jnp.sum(
            jax.lax.population_count(d).astype(jnp.int32), dtype=jnp.int32
        ),
    )
    probe(
        "and+popcount-sum",
        lambda d: jnp.sum(
            jax.lax.population_count(d[:, 0] & d[:, 1]).astype(jnp.int32),
            dtype=jnp.int32,
        ),
    )
    # Per-row partials instead of a full scalar reduce: if this is much
    # faster than and+popcount-sum, the scalar reduce is breaking XLA's
    # fusion (materializing the popcount array in HBM); measured, the
    # two track each other — the scalar reduce fuses fine.
    probe(
        "and+popcount-rowsum",
        lambda d: jnp.sum(
            jax.lax.population_count(d[:, 0] & d[:, 1]).astype(jnp.int32),
            axis=-1,
            dtype=jnp.int32,
        ),
    )

    # The raw kernel: XLA's fused bitwise+popcount+reduce (the only
    # path — there is no hand-written kernel).
    dev_s = time_variant("fused-XLA", plan.compiled_batched(expr, "count"))

    # --- tier 2: END-TO-END PQL through the executor -------------------
    # A real Holder with 954 fragments; the query arrives as PQL text and
    # runs the full dispatch: parse -> leaf resolution -> batch assembly
    # (cached across queries) -> fused program -> reduce.
    coalesce_stats = None
    topn_breakdown = None
    try:
        e2e_s, coalesce_stats, topn_breakdown = run_executor_tiers(
            leaves, host_count, rng, dev_s, cpu_fallback
        )
        metric = "e2e_pql_intersect_count_1b_columns"
    except Exception as e:  # noqa: BLE001 — recorded; the run exits 1
        tier_failed(
            "e2e-executor", f"{e!r:.400}; reporting the raw kernel metric"
        )
        if dev_s is None:
            raise
        e2e_s = dev_s
        metric = "intersect_count_1b_columns"

    # --- tier 5: HBM pressure (budget below total plane bytes) ---------
    hbm_pressure = None
    if os.environ.get("BENCH_SKIP_HBM_TIER") != "1":
        try:
            hbm_pressure = run_hbm_pressure_tier(rng, cpu_fallback)
        except Exception as e:  # noqa: BLE001 — recorded; the run exits 1
            tier_failed("hbm-pressure", f"{e!r:.300}")

    # --- tier 6: BSI Range/Sum over integer bit-planes -----------------
    bsi_tier = None
    if os.environ.get("BENCH_SKIP_BSI_TIER") != "1":
        try:
            bsi_tier = run_bsi_tier(rng, n_slices, cpu_fallback)
        except Exception as e:  # noqa: BLE001 — recorded; the run exits 1
            tier_failed("bsi", f"{e!r:.300}")

    # --- tier 6a: mixed DISTINCT-query storm, fusion on vs off --------
    # The plane-major multi-query fusion headline: a weighted mix of
    # distinct Count/Range/TopN trees under concurrent load, measured
    # with the interpreter fusion tier enabled and disabled.  The
    # kernel is memory-bound (47.7% of HBM peak raw), so every further
    # Gcols/s must come from amortizing passes across queries — this
    # tier is where that shows up or doesn't.
    mixed_storm = None
    if os.environ.get("BENCH_SKIP_MIXED_TIER") != "1":
        try:
            mixed_storm = run_mixed_storm_tier(rng, cpu_fallback)
        except Exception as e:  # noqa: BLE001 — recorded; the run exits 1
            tier_failed("mixed-storm", f"{e!r:.300}")

    # --- tier 6b: multi-node Intersect+Count, device-resident planes ---
    # BASELINE configs[4]'s distributed query (the reference's whole
    # point, executor.go:1149-1243) finally on the headline bench: real
    # in-process HTTP nodes sharing this process's accelerator, planes
    # device-resident, per-node-count throughput.
    cluster_tpu = None
    if os.environ.get("BENCH_SKIP_CLUSTER_TIER") != "1":
        try:
            cluster_tpu = run_cluster_tpu_tier(leaves, cpu_fallback)
        except Exception as e:  # noqa: BLE001 — recorded; the run exits 1
            tier_failed("cluster-tpu", f"{e!r:.300}")

    # --- tier 7: cold restart (time-to-first-answer while staging) -----
    cold_restart = None
    if os.environ.get("BENCH_SKIP_COLD_TIER") != "1":
        try:
            cold_restart = run_cold_restart_tier(rng, cpu_fallback)
            cold_restart.update(restart_probe)
        except Exception as e:  # noqa: BLE001 — recorded; the run exits 1
            tier_failed("cold-restart", f"{e!r:.300}")

    # --- tier 8: tiered storage (disk budget << total plane bytes) -----
    # The object-store cold tier (pilosa_tpu/tier): a skewed query
    # storm over more fragments than the disk budget admits, so the
    # LRU demotes and demand hydration pulls fragments back — versus
    # the identical storm unbounded.  Records hydration p50/p99, the
    # cold-hit rate, demotion/hydration cycle counts, and steady-state
    # query p99 vs the unbounded baseline.
    tiered = None
    if os.environ.get("BENCH_SKIP_TIERED_TIER") != "1":
        try:
            tiered = run_tiered_tier(rng, cpu_fallback)
        except Exception as e:  # noqa: BLE001 — recorded; the run exits 1
            tier_failed("tiered", f"{e!r:.300}")

    cols_per_s = total_columns / e2e_s
    vs = host_s / e2e_s
    # Effective traffic: 2 operands x 1/8 B/col, nothing written back.
    bytes_per_query = total_columns / 4
    hbm_peak = sanity_peak
    e2e_gbs = bytes_per_query / e2e_s / 1e9

    def pct_peak(gbs: float) -> str:
        return f" = {gbs*1e9/hbm_peak*100:.1f}% of HBM peak" if hbm_peak else ""

    if dev_s is not None:
        raw_gbs = bytes_per_query / dev_s / 1e9
        log(
            f"raw-kernel ceiling: {total_columns/dev_s/1e9:.1f} Gcols/s"
            f" ({raw_gbs:.0f} GB/s{pct_peak(raw_gbs)});"
            f" headline: {cols_per_s/1e9:.1f} Gcols/s"
            f" ({e2e_gbs:.0f} GB/s{pct_peak(e2e_gbs)})"
        )
    else:
        log(
            f"raw-kernel ceiling UNRELIABLE this run;"
            f" headline: {cols_per_s/1e9:.1f} Gcols/s"
            f" ({e2e_gbs:.0f} GB/s{pct_peak(e2e_gbs)})"
        )
    out = {
        "metric": metric,
        "value": round(cols_per_s / 1e9, 3),
        "unit": "Gcols/s",
        "vs_baseline": round(vs, 2),
        "effective_gb_s": round(e2e_gbs, 1),
        "device": {
            "platform": jax.devices()[0].platform,
            "kind": jax.devices()[0].device_kind,
            "count": len(jax.devices()),
        },
    }
    if dev_s is not None:
        out["raw_kernel_gb_s"] = round(bytes_per_query / dev_s / 1e9, 1)
        if stream_s is not None and stream_s > 0:
            # kernel-vs-floor: the fused kernel's bandwidth as a
            # fraction of the SAME-RUN streaming-reduce ceiling — the
            # skeptic-proof roofline figure (VERDICT r04 weak #5);
            # both read the same byte count, so the ratio is just
            # time-over-time.
            out["raw_kernel_vs_stream_floor"] = round(stream_s / dev_s, 3)
            # The raw and+popcount kernel's bandwidth as a percentage
            # of the stream floor.
            out["raw_kernel_floor_pct"] = round(100.0 * stream_s / dev_s, 1)
            out["stream_floor_gb_s"] = round(
                bytes_per_query / stream_s / 1e9, 1
            )
    if hbm_peak:
        out["pct_hbm_peak"] = round(e2e_gbs * 1e9 / hbm_peak * 100, 2)
        if dev_s is not None:
            out["raw_kernel_pct_hbm_peak"] = round(
                bytes_per_query / dev_s / 1e9 * 1e9 / hbm_peak * 100, 2
            )
    if coalesce_stats is not None:
        out["coalesce"] = coalesce_stats
    if topn_breakdown:
        out["topn_src_breakdown_p50_ms"] = topn_breakdown
    if hbm_pressure is not None:
        out["hbm_pressure"] = hbm_pressure
    if bsi_tier is not None:
        out["bsi"] = bsi_tier
    if mixed_storm is not None:
        out["mixed_storm"] = mixed_storm
    if cold_restart is not None:
        out["cold_restart"] = cold_restart
    if tiered is not None:
        out["tiered"] = tiered
    if cluster_reduce is not None:
        out["cluster_reduce"] = cluster_reduce
    if cluster_tpu is not None:
        out["cluster_tpu"] = cluster_tpu
    if mesh_scaling is not None:
        out["mesh_scaling"] = mesh_scaling
    if admission_storm is not None:
        out["admission_storm"] = admission_storm
    if rebalance_tier is not None:
        out["rebalance"] = rebalance_tier
    if replication_tier is not None:
        out["replication"] = replication_tier
    if degraded_tier is not None:
        out["degraded"] = degraded_tier
    if standing_tier is not None:
        out["standing"] = standing_tier
    if ingest_tier is not None:
        out["ingest"] = ingest_tier
    if sparse_tier is not None:
        out["sparse"] = sparse_tier
    if gameday_tier is not None:
        out["gameday"] = gameday_tier
    out["program_cache"] = {
        "entries": plan.program_cache_stats(),
        "bounds": plan.program_cache_bounds(),
    }
    # Launch telemetry snapshot (obs/perf.py): the per-site roofline
    # view — achieved GB/s (and % of measured stream floor when the
    # probe ran) for every device launch path the run exercised, plus
    # per-cache first-compile cost.  The bench asserts on this block
    # (tools/bench_smoke.py), so keep keys stable.
    try:
        from pilosa_tpu.obs import perf as perf_mod

        psnap = perf_mod.registry().snapshot()
        out["perf"] = {
            "floor_gbps": psnap.get("floor_gbps"),
            "sites": {
                name: {
                    "launches": s["launches"],
                    "gbps": s["gbps"],
                    "floor_pct": s.get("floor_pct"),
                }
                for name, s in psnap.get("sites", {}).items()
            },
            "compile_ms": plan.program_cache_compile_ms(),
        }
    except Exception as e:  # noqa: BLE001 — recorded; the run exits 1
        tier_failed("perf-snapshot", f"{e!r:.300}")
    out["failed_tiers"] = FAILED_TIERS
    print(json.dumps(out))
    if FAILED_TIERS:
        sys.exit(1)


def measure_query(
    ex, index, pq, check, n_serial=8, n_conc=48, threads=16, trials=3
):
    """Measure one warm query both ways; returns (p50_serial_s,
    per_query_concurrent_s, p50_under_load_s).  ``check(result)``
    asserts correctness on every single result.  The concurrent pass
    runs ``trials`` times and the BEST trial wins (every trial's
    results are still correctness-checked)."""
    import concurrent.futures

    def one(_i):
        t0 = time.perf_counter()
        res = ex.execute(index, pq)
        check(res)
        return time.perf_counter() - t0

    lat = [one(i) for i in range(n_serial)]
    p50 = sorted(lat)[len(lat) // 2] if lat else float("nan")
    best = (float("inf"), [])
    for _ in range(trials):
        with concurrent.futures.ThreadPoolExecutor(threads) as pool:
            t0 = time.perf_counter()
            conc_lat = list(pool.map(one, range(n_conc)))
            wall = time.perf_counter() - t0
        if wall < best[0]:
            best = (wall, conc_lat)
    wall, conc_lat = best
    per_q = wall / n_conc
    conc_p50 = sorted(conc_lat)[len(conc_lat) // 2]
    return p50, per_q, conc_p50


def run_tiered_tier(rng, cpu_fb=False) -> dict:
    """Tiered-storage scenario (pilosa_tpu/tier): local-FS store,
    disk budget set to ~1/3 of the hot fragment bytes (and the HBM
    budget to half the per-device plane bytes), then a SKEWED Count
    storm over every slice — the working set stays hot while the long
    tail cycles demote->hydrate — versus the identical storm
    unbounded.  The p99 ratio is the cost of serving an index that
    does not fit local storage; the demotion/hydration counters prove
    the cycle actually ran."""
    import jax

    from pilosa_tpu import device as device_mod
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.device.pool import PlanePool
    from pilosa_tpu.exec.executor import Executor
    from pilosa_tpu.obs.stats import ExpvarStatsClient
    from pilosa_tpu.ops import bitplane as bpl
    from pilosa_tpu.pql.parser import parse_string
    from pilosa_tpu.tier import LocalFSStore, TierManager

    n_dev = max(1, len(jax.local_devices()))
    n_slices = 12 if cpu_fb else 32
    rows = 16  # pad_rows(16) x 128 KiB = 2 MiB plane per fragment
    n_queries = n_slices * (6 if cpu_fb else 10)
    hot_set = max(2, n_slices // 4)

    with tempfile.TemporaryDirectory() as d:
        holder = Holder(os.path.join(d, "data"))
        holder.open()
        idx = holder.create_index("tiered")
        fr = idx.create_frame("t", cache_size=256)
        view = fr.create_view_if_not_exists("standard")
        planes = rng.integers(
            0, 2**32, size=(n_slices, rows, bpl.WORDS_PER_SLICE),
            dtype=np.uint32,
        )
        for s in range(n_slices):
            frag = view.create_fragment_if_not_exists(s)
            prime_fragment(frag, planes[s], bpl.pad_rows)
            frag.snapshot()  # disk accounting needs the real file bytes
        want = {
            s: int(np.bitwise_count(planes[s][0]).sum())
            for s in range(n_slices)
        }
        total_disk = sum(
            os.path.getsize(view.fragment(s).path) for s in range(n_slices)
        )
        plane_bytes = view.fragment(0)._plane.nbytes
        per_dev = (n_slices + n_dev - 1) // n_dev
        hbm_budget = per_dev * plane_bytes // 2
        pq = parse_string("Count(Bitmap(rowID=0, frame=t))")

        # 80% of queries hit the hot quarter, 20% sweep the tail — the
        # access pattern tiering exists for.
        seq = [
            int(rng.integers(0, hot_set))
            if rng.random() < 0.8
            else int(rng.integers(0, n_slices))
            for _ in range(n_queries)
        ]

        def storm(mgr) -> list:
            lats = []
            ex = Executor(holder, host="localhost:0")
            try:
                for s in seq:
                    t0 = time.perf_counter()
                    (n,) = ex.execute("tiered", pq, slices=[s])
                    lats.append(time.perf_counter() - t0)
                    assert n == want[s], (s, n, want[s])
            finally:
                ex.close()
            lats.sort()
            return lats

        def pcts(lats) -> dict:
            return {
                "p50_ms": round(lats[len(lats) // 2] * 1e3, 2),
                "p99_ms": round(
                    lats[min(len(lats) - 1, int(len(lats) * 0.99))] * 1e3, 2
                ),
            }

        # Warm compiles outside any timed window (shared fixed cost).
        warm_ex = Executor(holder, host="localhost:0")
        try:
            for s in range(n_slices):
                warm_ex.execute("tiered", pq, slices=[s])
        finally:
            warm_ex.close()

        out = {
            "n_fragments": n_slices,
            "total_disk_mib": round(total_disk / 2**20, 2),
        }
        baseline = pcts(storm(None))
        out["unbounded"] = baseline

        stats = ExpvarStatsClient()
        store = LocalFSStore(os.path.join(d, "store"), stats=stats)
        disk_budget = max(1, total_disk // 3)
        mgr = TierManager(
            holder, store, stats=stats, disk_budget_bytes=disk_budget
        )
        mgr.attach_all()
        mgr.upload_all(include_schema=False)
        pool = PlanePool(budget_bytes=hbm_budget)
        prev = device_mod._set_pool(pool)
        try:
            mgr.enforce_disk_budget()  # initial demotion to budget
            lats = storm(mgr)
            # drain the async budget sweeps before reading counters
            t0 = time.monotonic()
            while mgr._enforcing and time.monotonic() - t0 < 30:
                time.sleep(0.05)
        finally:
            device_mod._set_pool(prev)
        snap = stats.snapshot()
        counts = snap.get("counts", {})
        hyd = snap.get("histograms", {}).get("tier.hydrateMs", {})
        tier = pcts(lats)
        tier.update(
            {
                "disk_budget_mib": round(disk_budget / 2**20, 2),
                "hbm_budget_mib": round(hbm_budget / 2**20, 2),
                "demotions": counts.get("tier.demotions", 0),
                "hydrations": counts.get("tier.hydrations", 0),
                "cold_hit_rate": round(
                    counts.get("tier.hydrations", 0) / len(seq), 3
                ),
                "hydrate_p50_ms": round(hyd.get("p50", 0.0), 2),
                "hydrate_p99_ms": round(hyd.get("p99", 0.0), 2),
            }
        )
        out["tiered"] = tier
        out["p99_ratio"] = (
            round(tier["p99_ms"] / baseline["p99_ms"], 2)
            if baseline["p99_ms"]
            else None
        )
        log(
            f"tiered: disk budget {tier['disk_budget_mib']} MiB of"
            f" {out['total_disk_mib']} MiB total; p50"
            f" {tier['p50_ms']:.2f} ms p99 {tier['p99_ms']:.2f} ms"
            f" ({out['p99_ratio']}x unbounded p99"
            f" {baseline['p99_ms']:.2f} ms); {tier['demotions']}"
            f" demotions, {tier['hydrations']} hydrations (cold-hit"
            f" rate {tier['cold_hit_rate']}), hydrate p50"
            f" {tier['hydrate_p50_ms']} ms p99 {tier['hydrate_p99_ms']} ms"
        )
        holder.close()
        return out


def run_hbm_pressure_tier(rng, cpu_fb=False) -> dict:
    """HBM-pressure scenario (device/pool.py): per-device budget set to
    HALF the per-device plane bytes, then a per-slice TopN sweep over
    more fragments than fit — versus the identical sweep unbounded.
    Reports evictions, prefetch hit rate, and p50/p99 query latency for
    both, so the cost of paging planes HBM<->host under pressure is a
    tracked number, not a guess."""
    import jax

    from pilosa_tpu import device as device_mod
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.device.pool import PlanePool
    from pilosa_tpu.exec.executor import Executor
    from pilosa_tpu.ops import bitplane as bpl
    from pilosa_tpu.pql.parser import parse_string

    n_dev = max(1, len(jax.local_devices()))
    n_slices = 16 if cpu_fb else 32
    rows = 16  # pad_rows(16) x 128 KiB = 2 MiB plane per fragment
    rounds = 2 if cpu_fb else 3

    with tempfile.TemporaryDirectory() as d:
        holder = Holder(d)
        holder.open()
        idx = holder.create_index("hbm")
        fr = idx.create_frame("h", cache_size=256)
        view = fr.create_view_if_not_exists("standard")
        planes = rng.integers(
            0, 2**32, size=(n_slices, rows, bpl.WORDS_PER_SLICE),
            dtype=np.uint32,
        )
        for s in range(n_slices):
            prime_fragment(
                view.create_fragment_if_not_exists(s), planes[s], bpl.pad_rows
            )
        frags = [view.fragment(s) for s in range(n_slices)]
        plane_bytes = frags[0]._plane.nbytes
        per_dev = (n_slices + n_dev - 1) // n_dev
        budget = per_dev * plane_bytes // 2
        pq = parse_string("TopN(Bitmap(rowID=0, frame=h), frame=h, n=8)")

        def sweep(pool) -> list:
            # Cold mirrors per variant: the comparison is paging cost,
            # not residual warmth from the previous variant.
            for frag in frags:
                frag._invalidate_device()
            lats = []
            ex = Executor(
                holder,
                host="localhost:0",
                prefetcher=device_mod.Prefetcher(pool=pool),
            )
            try:
                for _ in range(rounds):
                    for s in range(n_slices):
                        t0 = time.perf_counter()
                        (pairs,) = ex.execute("hbm", pq, slices=[s])
                        lats.append(time.perf_counter() - t0)
                        assert len(pairs) == 8
            finally:
                ex.close()
            lats.sort()
            return lats

        # One warm sweep outside any timed window: compiles and
        # first-touch-per-device dispatch are fixed costs shared by both
        # variants, not part of the paging story (sweep() re-colds the
        # mirrors, so the timed variants still pay their own uploads).
        warm_ex = Executor(holder, host="localhost:0")
        try:
            for s in range(n_slices):
                warm_ex.execute("hbm", pq, slices=[s])
        finally:
            warm_ex.close()

        out = {
            "n_fragments": n_slices,
            "plane_mib": round(plane_bytes / 2**20, 2),
            "budget_mib_per_device": round(budget / 2**20, 2),
        }
        for label, b in (("unbounded", 0), ("budgeted", budget)):
            pool = PlanePool(budget_bytes=b)
            prev = device_mod._set_pool(pool)
            try:
                lats = sweep(pool)
            finally:
                device_mod._set_pool(prev)
            snap = pool.snapshot()
            c = snap["counters"]
            fetches = c["prefetchHit"] + c["prefetchMiss"]
            tier = {
                "p50_ms": round(lats[len(lats) // 2] * 1e3, 2),
                "p99_ms": round(
                    lats[min(len(lats) - 1, int(len(lats) * 0.99))] * 1e3, 2
                ),
                "evictions": c["evictions"],
                "prefetch_hit_rate": (
                    round(c["prefetchHit"] / fetches, 3) if fetches else None
                ),
                "max_resident_mib": round(
                    max(
                        (dv["max_resident_bytes"] for dv in snap["devices"]),
                        default=0,
                    )
                    / 2**20,
                    2,
                ),
            }
            out[label] = tier
            log(
                f"hbm-pressure {label}: p50 {tier['p50_ms']:.2f} ms,"
                f" p99 {tier['p99_ms']:.2f} ms, evictions"
                f" {tier['evictions']}, prefetch hit rate"
                f" {tier['prefetch_hit_rate']}, max resident"
                f" {tier['max_resident_mib']} MiB"
                f" (budget {out['budget_mib_per_device']} MiB/device)"
            )
        holder.close()
        return out


def run_cluster_tpu_tier(leaves, cpu_fb=False) -> dict:
    """``cluster_tpu`` tier: BASELINE configs[4]'s multi-node
    Intersect+Count with device-resident planes.  Boots 1/2/4 real
    in-process servers (own HTTP listener, holder, executor; static
    hash-identical placement) sharing THIS process's accelerator,
    primes each node's owned slices, warms the mirrors onto the device,
    and measures the same PQL through the coordinator — sync p50 plus
    concurrent ms/query and Gcols/s per node count.  With >1 device
    visible the tier additionally records the node × device GRID (the
    production topology: each node's local map leg runs the
    mesh-sharded plane over its owned slices), keyed "NxM"; the full
    process-isolated grid over the virtual mesh is the mesh_scaling
    tier's node_grid (tools/mesh_bench.py)."""
    import jax

    from pilosa_tpu.ops import bitplane as bp
    from pilosa_tpu.parallel import mesh as pmesh

    n_slices = min(
        len(leaves), int(os.environ.get("BENCH_CLUSTER_TPU_SLICES", "128"))
    )
    rows = leaves[:n_slices]
    want = int(np.bitwise_count(rows[:, 0] & rows[:, 1]).sum())
    q = 'Count(Intersect(Bitmap(rowID=0, frame="f"), Bitmap(rowID=1, frame="f")))'
    n_local = len(jax.local_devices())
    device_counts = [d for d in (1, 2, 4, 8) if d <= n_local] or [1]
    out: dict = {
        "slices": n_slices,
        "devices_visible": n_local,
        "per_node": {},
        "grid": {},
    }
    quiet = dict(
        anti_entropy_interval=3600,
        polling_interval=3600,
        cache_flush_interval=3600,
        prewarm=False,
    )
    for m_devices in device_counts:
        bp.configure_mesh_devices(m_devices)
        pmesh._slices_mesh = None
        try:
            out["grid"].update(
                _cluster_tpu_node_rows(
                    rows, want, q, quiet, m_devices, out["per_node"]
                )
            )
        finally:
            bp.configure_mesh_devices(0)
            pmesh._slices_mesh = None
    return out


def _cluster_tpu_node_rows(
    rows, want, q, quiet, m_devices, per_node
) -> dict:
    """One device-width column of the cluster_tpu grid; also fills the
    legacy ``per_node`` table when running at the widest mesh."""
    import tempfile
    from concurrent.futures import ThreadPoolExecutor

    from pilosa_tpu.cluster.topology import Cluster
    from pilosa_tpu.net.client import InternalClient
    from pilosa_tpu.net.server import Server
    from pilosa_tpu.ops import bitplane as bp
    from pilosa_tpu.ops.bitplane import SLICE_WIDTH

    n_slices = rows.shape[0]
    grid: dict = {}
    for n_nodes in (1, 2, 4):
        with tempfile.TemporaryDirectory() as td:
            servers = []
            clusters = []
            try:
                for i in range(n_nodes):
                    cluster = Cluster(replica_n=1)
                    s = Server(
                        data_dir=os.path.join(td, f"n{i}"),
                        cluster=cluster,
                        **quiet,
                    )
                    s.open()
                    servers.append(s)
                    clusters.append(cluster)
                hosts = sorted(s.host for s in servers)
                for c in clusters:
                    for h in hosts:
                        if c.node_by_host(h) is None:
                            c.add_node(h)
                    c.nodes.sort(key=lambda n: n.host)
                for s in servers:
                    holder = s.holder
                    holder.create_index_if_not_exists("i")
                    holder.index("i").create_frame_if_not_exists("f")
                    view = holder.frame("i", "f").create_view_if_not_exists(
                        "standard"
                    )
                    for sl in s.cluster.owns_slices(
                        "i", n_slices - 1, s.host
                    ):
                        prime_fragment(
                            view.create_fragment_if_not_exists(sl),
                            rows[sl],
                            bp.pad_rows,
                        )
                    holder.index("i").set_remote_max_slice(n_slices - 1)
                coord = servers[0].host
                client = InternalClient(coord, timeout=120.0)
                # Warm: compiles + host->device mirror uploads; planes
                # stay device-resident for the measured queries.
                got = int(client.execute_query("i", q)[0])
                assert got == want, f"cluster bit-exactness: {got} != {want}"
                times = []
                for _ in range(9):
                    t0 = time.perf_counter()
                    client.execute_query("i", q)
                    times.append(time.perf_counter() - t0)
                times.sort()
                p50 = times[len(times) // 2]
                n_conc, threads = 48, 16
                clients = [
                    InternalClient(coord, timeout=120.0)
                    for _ in range(threads)
                ]
                t0 = time.perf_counter()
                with ThreadPoolExecutor(max_workers=threads) as pool:
                    list(
                        pool.map(
                            lambda i: clients[i % threads].execute_query(
                                "i", q
                            ),
                            range(n_conc),
                        )
                    )
                conc_s = (time.perf_counter() - t0) / n_conc
                gcols = n_slices * SLICE_WIDTH / conc_s / 1e9
                row = {
                    "sync_p50_ms": round(p50 * 1e3, 3),
                    "concurrent_ms_per_query": round(conc_s * 1e3, 3),
                    "gcols_per_s": round(gcols, 3),
                }
                grid[f"{n_nodes}x{m_devices}"] = dict(
                    row, nodes=n_nodes, devices_per_node=m_devices
                )
                # Device widths run ascending, so the legacy per_node
                # table ends up recording the WIDEST mesh's figures.
                per_node[str(n_nodes)] = row
                log(
                    f"cluster_tpu {n_nodes} node(s) x {m_devices} "
                    f"device(s): sync p50 {p50*1e3:.2f} ms, concurrent "
                    f"{conc_s*1e3:.2f} ms/query, {gcols:.2f} Gcols/s"
                )
            finally:
                for s in servers:
                    s.close()
    return grid


def run_bsi_tier(rng, n_slices, cpu_fb=False) -> dict:
    """``bsi`` tier: BSI Range + Sum over the standard corpus slice
    count.  A depth-8 integer field (every column valued, uniform
    0..255) plane-injected into a range-enabled frame; measures
    ``Count(Range(v > 100))`` and ``Sum(field=v)`` end to end through
    the executor with the coalescer on (the production path), reporting
    Gcols/s + ms/query.  Expected results come from an independent host
    computation over the injected planes, so the tier is also a
    bit-exactness anchor at corpus scale."""
    from pilosa_tpu import bsi
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.exec.coalesce import CoalesceScheduler
    from pilosa_tpu.exec.executor import Executor
    from pilosa_tpu.ops import bitplane as bpl
    from pilosa_tpu.pql.parser import parse_string

    depth = 8
    pred = 100
    trim = dict(n_serial=2, trials=1) if cpu_fb else dict(n_serial=8, trials=3)
    total_columns = n_slices * bpl.SLICE_WIDTH
    with tempfile.TemporaryDirectory() as d:
        holder = Holder(d)
        holder.open()
        idx = holder.create_index("b")
        fr = idx.create_frame("fb")
        fr.set_options(range_enabled=True)
        fr.create_field("v", 0, (1 << depth) - 1)
        view = fr.create_view_if_not_exists(bsi.field_view_name("v"))
        planes = rng.integers(
            0, 2**32, size=(n_slices, depth, bpl.WORDS_PER_SLICE),
            dtype=np.uint32,
        )
        ones = np.full((1, bpl.WORDS_PER_SLICE), 0xFFFFFFFF, np.uint32)
        zeros = np.zeros((1, bpl.WORDS_PER_SLICE), np.uint32)
        for s in range(n_slices):
            prime_fragment(
                view.create_fragment_if_not_exists(s),
                np.concatenate([ones, zeros, planes[s]]),
                bpl.pad_rows,
            )

        # Host reference, straight from the planes: Sum is the weighted
        # plane dot; the Range count rides the gt ripple in numpy.
        plane_pops = np.bitwise_count(planes).sum(axis=-1, dtype=np.int64)
        want_sum = int(sum((1 << k) * int(plane_pops[:, k].sum()) for k in range(depth)))
        gt = np.zeros((n_slices, bpl.WORDS_PER_SLICE), np.uint32)
        eq = np.full((n_slices, bpl.WORDS_PER_SLICE), 0xFFFFFFFF, np.uint32)
        for k in reversed(range(depth)):
            b = planes[:, k]
            if (pred >> k) & 1:
                eq_new = eq & b
            else:
                gt = gt | (eq & b)
                eq_new = eq & ~b
            eq = eq_new
        want_gt = int(np.bitwise_count(gt).sum())

        co = CoalesceScheduler()
        ex = Executor(holder, host="localhost:0", coalescer=co)
        out = {
            "depth": depth,
            "bucket": bsi.pad_depth(depth),
            "columns": total_columns,
        }
        try:
            rq = parse_string(f"Count(Range(frame=fb, v > {pred}))")
            sq = parse_string("Sum(frame=fb, field=v)")

            def check_range(res):
                assert int(res[0]) == want_gt, f"bsi Range exactness: {res[0]}"

            def check_sum(res):
                vc = res[0]
                assert (int(vc.value), int(vc.count)) == (
                    want_sum,
                    total_columns,
                ), f"bsi Sum exactness: {vc}"

            for label, pq, check in (
                ("range", rq, check_range),
                ("sum", sq, check_sum),
            ):
                t0 = time.perf_counter()
                (got,) = ex.execute("b", pq)
                check([got])
                cold_s = time.perf_counter() - t0
                p50, per_q, conc_p50 = measure_query(
                    ex, "b", pq, check, n_conc=8 if cpu_fb else 32, **trim
                )
                tier = {
                    "cold_ms": round(cold_s * 1e3, 2),
                    "ms_per_query": round(p50 * 1e3, 3),
                    "concurrent_ms_per_query": round(per_q * 1e3, 3),
                    "p50_under_load_ms": round(conc_p50 * 1e3, 3),
                    "gcols_s": round(total_columns / per_q / 1e9, 3),
                    "sync_gcols_s": round(total_columns / p50 / 1e9, 3),
                }
                out[label] = tier
                log(
                    f"bsi {label} (depth {depth}): cold {tier['cold_ms']:.1f} ms;"
                    f" sync p50 {tier['ms_per_query']:.2f} ms/query"
                    f" ({tier['sync_gcols_s']:.2f} Gcols/s); concurrent"
                    f" {tier['concurrent_ms_per_query']:.2f} ms/query"
                    f" ({tier['gcols_s']:.2f} Gcols/s)"
                )
            snap = co.snapshot()
            out["coalesce_launches"] = snap["launches"]
            out["coalesced_queries"] = snap["queries"]
        finally:
            ex.close()
            co.close()
            holder.close()
        return out


def run_mixed_storm_tier(rng, cpu_fb=False) -> dict:
    """``mixed_storm`` tier: a weighted mix of DISTINCT Count / Range /
    TopN queries under concurrent load, fusion ON vs OFF.

    Before this tier, the concurrent figures all measured storms of
    ONE query shape — exactly what the per-compile-key coalescer
    batches.  A realistic mix of distinct trees never shared a launch:
    this tier boots the same executor twice (CoalesceScheduler with
    ``fuse`` enabled/disabled), runs the identical mix at each
    concurrency step, and records Gcols/s, coalesce launches, fused
    queries per launch, and the interpreter program-cache entries —
    including after a second, more-diverse mix, which must NOT grow
    them (opcode tables are data; the jit key is pure geometry).
    Every worker checks its result against the direct (uncoalesced)
    executor's answer, so the speedup is anchored to byte-identical
    results."""
    import concurrent.futures

    from pilosa_tpu import bsi
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.exec import plan
    from pilosa_tpu.exec.coalesce import CoalesceScheduler
    from pilosa_tpu.exec.executor import Executor
    from pilosa_tpu.ops import bitplane as bpl
    from pilosa_tpu.pql.parser import parse_string

    n_slices = 8 if cpu_fb else 64
    depth = 8
    total_columns = n_slices * bpl.SLICE_WIDTH
    with tempfile.TemporaryDirectory() as d:
        holder = Holder(d)
        holder.open()
        idx = holder.create_index("ms")
        fr = idx.create_frame("f", cache_size=256)
        view = fr.create_view_if_not_exists("standard")
        rows = rng.integers(
            0, 2**32, size=(n_slices, 8, bpl.WORDS_PER_SLICE), dtype=np.uint32
        )
        for s in range(n_slices):
            prime_fragment(
                view.create_fragment_if_not_exists(s), rows[s], bpl.pad_rows
            )
        fr.set_options(range_enabled=True)
        fr.create_field("v", 0, (1 << depth) - 1)
        bview = fr.create_view_if_not_exists(bsi.field_view_name("v"))
        planes = rng.integers(
            0, 2**32, size=(n_slices, depth, bpl.WORDS_PER_SLICE),
            dtype=np.uint32,
        )
        ones = np.full((1, bpl.WORDS_PER_SLICE), 0xFFFFFFFF, np.uint32)
        zeros = np.zeros((1, bpl.WORDS_PER_SLICE), np.uint32)
        for s in range(n_slices):
            prime_fragment(
                bview.create_fragment_if_not_exists(s),
                np.concatenate([ones, zeros, planes[s]]),
                bpl.pad_rows,
            )
        ft = idx.create_frame("t", cache_size=512)
        tview = ft.create_view_if_not_exists("standard")
        trows = rng.integers(
            0, 2**32, size=(n_slices, 32, bpl.WORDS_PER_SLICE),
            dtype=np.uint32,
        )
        for s in range(n_slices):
            prime_fragment(
                tview.create_fragment_if_not_exists(s), trows[s], bpl.pad_rows
            )

        # The weighted mix: ~60% point counts, ~25% BSI ranges, ~15%
        # TopN(src) — every entry a DISTINCT tree or predicate.
        count_qs = [
            f"Count(Intersect(Bitmap(rowID={i}, frame=f),"
            f" Bitmap(rowID={j}, frame=f)))"
            for i, j in ((0, 1), (1, 2), (2, 3), (3, 4))
        ] + [
            f"Count(Union(Bitmap(rowID={i}, frame=f),"
            f" Bitmap(rowID={j}, frame=f)))"
            for i, j in ((4, 5), (5, 6))
        ]
        range_qs = [
            f"Count(Range(frame=f, v > {p}))" for p in (20, 80, 140, 200)
        ]
        topn_qs = [
            f"TopN(Bitmap(rowID={i}, frame=t), frame=t, n=8)"
            for i in (0, 1, 2)
        ]
        mix = count_qs * 2 + range_qs + topn_qs
        parsed = {q: parse_string(q) for q in {*mix}}

        def canon(res):
            if hasattr(res, "bits"):
                return ("bits", tuple(res.bits()))
            if isinstance(res, list):
                return ("pairs", tuple((p.id, p.count) for p in res))
            return ("val", int(res))

        direct = Executor(holder, host="localhost:0")
        try:
            want = {
                q: canon(direct.execute("ms", pq)[0])
                for q, pq in parsed.items()
            }
        finally:
            direct.close()

        tiers = (8,) if cpu_fb else (16, 64, 128)
        per_tier_mult = 3 if cpu_fb else 6

        def run_setting(fuse_on: bool) -> dict:
            co = CoalesceScheduler(fuse=fuse_on)
            ex = Executor(holder, host="localhost:0", coalescer=co)
            setting: dict = {}
            try:
                # Warm serial (batch caches + direct program compiles),
                # then one untimed mini-storm so the fused interpreter
                # geometries compile OUTSIDE the measured windows.
                for q, pq in parsed.items():
                    got = canon(ex.execute("ms", pq)[0])
                    assert got == want[q], f"mixed_storm identity: {q}"

                def one(i):
                    q = mix[i % len(mix)]
                    got = canon(ex.execute("ms", parsed[q])[0])
                    assert got == want[q], f"mixed_storm identity: {q}"

                with concurrent.futures.ThreadPoolExecutor(8) as pool:
                    list(pool.map(one, range(2 * len(mix))))
                for conc in tiers:
                    n_q = conc * per_tier_mult
                    before = co.snapshot()
                    t0 = time.perf_counter()
                    with concurrent.futures.ThreadPoolExecutor(conc) as pool:
                        list(pool.map(one, range(n_q)))
                    wall = time.perf_counter() - t0
                    snap = co.snapshot()
                    launches = snap["launches"] - before["launches"]
                    fused_q = snap["fused_queries"] - before["fused_queries"]
                    fused_l = (
                        snap["fused_launches"] - before["fused_launches"]
                    )
                    gcols = n_q * total_columns / wall / 1e9
                    setting[str(conc)] = {
                        "queries": n_q,
                        "gcols_s": round(gcols, 3),
                        "ms_per_query": round(wall / n_q * 1e3, 3),
                        "launches": launches,
                        "fused_launches": fused_l,
                        "fused_queries": fused_q,
                        "fused_per_launch": (
                            round(fused_q / fused_l, 2) if fused_l else None
                        ),
                    }
                    log(
                        f"mixed_storm fuse={'on' if fuse_on else 'off'}"
                        f" conc={conc}: {gcols:.2f} Gcols/s,"
                        f" {launches} launches for {n_q} queries"
                        f" ({fused_q} fused over {fused_l} interp launches)"
                    )
                setting["fetch_launches"] = co.snapshot()["fetch_launches"]
            finally:
                ex.close()
                co.close()
            return setting

        out: dict = {
            "slices": n_slices,
            "columns": total_columns,
            "distinct_queries": len(parsed),
            "errors": 0,
        }
        out["fusion_on"] = run_setting(True)
        entries_on = plan.program_cache_stats()["interp"]
        out["fusion_off"] = run_setting(False)
        out["speedup"] = {
            str(c): round(
                out["fusion_on"][str(c)]["gcols_s"]
                / out["fusion_off"][str(c)]["gcols_s"],
                3,
            )
            for c in tiers
            if out["fusion_off"][str(c)]["gcols_s"]
        }
        out["interp_entries"] = entries_on

        # Diversity probe: a SECOND fused storm over a strictly more
        # diverse mix (new predicates, new row pairs, new TopN rows)
        # must leave the interpreter's compiled-entry count unchanged —
        # expression tables are data, geometry is the only jit key.
        div_qs = [
            f"Count(Range(frame=f, v > {p}))"
            for p in (5, 33, 77, 111, 155, 199, 233, 250)
        ] + [
            f"Count(Intersect(Bitmap(rowID={i}, frame=f),"
            f" Bitmap(rowID={j}, frame=f)))"
            for i, j in ((0, 7), (1, 6), (2, 5), (3, 7))
        ]
        div_parsed = [parse_string(q) for q in div_qs]
        co = CoalesceScheduler(fuse=True)
        ex = Executor(holder, host="localhost:0", coalescer=co)
        try:
            with concurrent.futures.ThreadPoolExecutor(8) as pool:
                list(
                    pool.map(
                        lambda i: ex.execute(
                            "ms", div_parsed[i % len(div_parsed)]
                        ),
                        range(3 * len(div_parsed)),
                    )
                )
        finally:
            ex.close()
            co.close()
        out["interp_entries_after_diversity"] = plan.program_cache_stats()[
            "interp"
        ]
        log(
            f"mixed_storm: speedup {out['speedup']}; interp program-cache"
            f" entries {entries_on} ->"
            f" {out['interp_entries_after_diversity']} after diversity"
        )
        holder.close()
        return out


def run_cold_restart_tier(rng, cpu_fb=False) -> dict:
    """``cold_restart`` tier: the rolling-restart fast path.  Builds a
    node's data dir, warms its mirrors (the pre-restart incarnation,
    residency table persisted at close), then "restarts" — fresh
    residency pool, holder reopened from disk — and measures
    time-to-first-answer while the lazy background staging lane
    (device/prefetch.py, ordered by the persisted residency table)
    streams the mirrors up, plus staging-complete time and programs
    compiled in the window.  Tracks the 4.79 s eager-staging cold e2e
    this path replaces (VERDICT item 4); the fresh-process compile
    numbers ride in from tools/compile_probe_restart.py."""
    from pilosa_tpu import device as device_mod
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.exec import plan
    from pilosa_tpu.exec.executor import Executor
    from pilosa_tpu.ops import bitplane as bpl
    from pilosa_tpu.pql.parser import parse_string

    n_slices = 8 if cpu_fb else 64
    bits_per_row = 256
    with tempfile.TemporaryDirectory() as d:
        holder = Holder(d)
        holder.open()
        idx = holder.create_index("i")
        f = idx.create_frame("f")
        view = f.create_view_if_not_exists("standard")
        for s in range(n_slices):
            frag = view.create_fragment_if_not_exists(s)
            base = s * bpl.SLICE_WIDTH
            for r in (1, 2):
                for c in rng.integers(0, bpl.SLICE_WIDTH, size=bits_per_row):
                    frag.set_bit(r, base + int(c))
            frag.flush_ops()
        holder.warm_device_mirrors()
        holder.close()  # persists the residency table

        # "Restart": device state gone (fresh pool), data re-opened
        # from disk, serving starts immediately, staging drains behind.
        prev_pool = device_mod._set_pool(device_mod.PlanePool())
        try:
            progs_before = plan.program_cache_entries()
            t0 = time.perf_counter()
            h2 = Holder(d)
            h2.open()
            pf = device_mod.Prefetcher()
            job = h2.stage_device_mirrors(pf)
            ex = Executor(h2, prefetcher=pf)
            pq = parse_string(
                "Count(Intersect(Bitmap(rowID=1, frame=f),"
                " Bitmap(rowID=2, frame=f)))"
            )
            (got,) = ex.execute("i", pq)
            t_first = time.perf_counter() - t0
            in_flight = not job.done()
            job.wait()
            t_staged = time.perf_counter() - t0
            progs = plan.program_cache_entries() - progs_before
            tier = {
                "slices": n_slices,
                "first_answer_ms": round(t_first * 1e3, 2),
                "staging_in_flight_at_first_answer": in_flight,
                "staging_complete_ms": round(t_staged * 1e3, 2),
                "staging": job.snapshot(),
                "programs_compiled": progs,
                "count": int(got),
            }
            log(
                f"cold restart ({n_slices} slices): first answer"
                f" {tier['first_answer_ms']:.0f} ms (staging in flight:"
                f" {in_flight}); staging complete"
                f" {tier['staging_complete_ms']:.0f} ms;"
                f" {progs} programs compiled in the window"
            )
            ex.close()
            h2.close()
        finally:
            device_mod._set_pool(prev_pool)
        return tier


def run_executor_tiers(leaves, host_count, rng, dev_s, cpu_fb=False):
    """Executor tiers; returns ``(e2e_s, coalesce_stats)`` — the e2e
    per-query seconds under concurrent load (the throughput the
    north-star metric names) and the coalescer's per-tier launch /
    occupancy record for the artifact.

    ``dev_s`` may be None when the raw-kernel slope was unreliable (the
    "x raw kernel" annotations degrade gracefully).  ``cpu_fb`` is
    main()'s held-to-the-CPU flag: trimmed iteration counts."""
    import jax  # noqa: F401 — backend already up
    # One trim policy for every fallback-shortened tier.
    trim = dict(n_serial=2, trials=1) if cpu_fb else dict(n_serial=8, trials=3)
    from pilosa_tpu.exec.coalesce import CoalesceScheduler
    from pilosa_tpu.exec.executor import Executor
    from pilosa_tpu.pql.parser import parse_string

    # The coalescer under test is the production configuration: the
    # concurrent tiers below are exactly the query storms it exists for,
    # and its launches/occupancy land in the artifact so the perf
    # trajectory shows WHERE the throughput came from.
    co = CoalesceScheduler()
    coalesce_stats = {"tiers": {}}

    def co_tier(label: str, queries: int, before: dict) -> dict:
        snap = co.snapshot()
        launches = snap["launches"] - before["launches"]
        qn = snap["queries"] - before["queries"]
        tier = {
            "launches": launches,
            "coalesced_queries": qn,
            "mean_batch_occupancy": (
                round(qn / launches, 2) if launches else None
            ),
            "dispatches_per_query": (
                round(launches / queries, 3) if queries else None
            ),
            "pad_rows": snap["pad_rows"] - before["pad_rows"],
        }
        coalesce_stats["tiers"][label] = tier
        log(
            f"coalesce {label}: {launches} launches for {qn} queries ->"
            f" mean occupancy {tier['mean_batch_occupancy']},"
            f" {tier['dispatches_per_query']} dispatches/query"
        )
        return snap

    from pilosa_tpu.obs.trace import Tracer

    tr = Tracer(capacity=64)
    with tempfile.TemporaryDirectory() as d:
        holder = build_holder(leaves, d)
        ex = Executor(holder, host="localhost:0", coalescer=co, tracer=tr)
        pq = parse_string("Count(Intersect(Bitmap(rowID=1, frame=f), Bitmap(rowID=2, frame=f)))")
        t0 = time.perf_counter()
        (got,) = ex.execute("i", pq)
        cold_s = time.perf_counter() - t0
        assert int(got) == host_count, f"e2e bit-exactness: {got} != {host_count}"
        from pilosa_tpu.exec import warmup as _warmup

        cache_note = (
            ", persistent cache on" if _warmup.enabled_cache_dir() else ""
        )
        log(f"e2e executor COLD (assembly+compile{cache_note}): {cold_s*1e3:.1f} ms")

        def check_count(res):
            assert int(res[0]) == host_count, f"e2e bit-exactness: {res[0]}"

        co_before = co.snapshot()
        n_conc_16 = 16 if cpu_fb else 48
        p50, e2e_16, conc_p50 = measure_query(
            ex, "i", pq, check_count, n_conc=n_conc_16, **trim
        )
        log(
            f"e2e executor Intersect+Count: sync p50 {p50*1e3:.2f} ms/query"
            f" (incl. dispatch + fetch); CONCURRENT(16) {e2e_16*1e3:.2f}"
            f" ms/query throughput, p50 latency under load"
            f" {conc_p50*1e3:.2f} ms"
            + (f" ({e2e_16/dev_s:.2f}x raw kernel)" if dev_s else "")
        )
        co_before = co_tier(
            "count_concurrent_16",
            trim["n_serial"] + trim["trials"] * n_conc_16,
            co_before,
        )
        # Climb the thread ladder: more clients overlap the per-query
        # host work and fetch waits until the engine is the limiter.
        tiers = {16: e2e_16}
        for threads in () if cpu_fb else (64, 128):
            _, per_q, _ = measure_query(
                ex, "i", pq, check_count,
                n_serial=0, n_conc=3 * threads, threads=threads,
            )
            tiers[threads] = per_q
            log(
                f"e2e executor Intersect+Count CONCURRENT({threads}):"
                f" {per_q*1e3:.2f} ms/query throughput"
                + (f" ({per_q/dev_s:.2f}x raw kernel)" if dev_s else "")
            )
            co_before = co_tier(
                f"count_concurrent_{threads}", 3 * 3 * threads, co_before
            )
        best_t = min(tiers, key=tiers.get)
        e2e_s = tiers[best_t]
        log(f"e2e headline uses the {best_t}-thread figure")

        # --- tier 3: TopN through the executor --------------------------
        # 2048 ranked-cache candidate rows in one fragment, scored against
        # a src row (reference: executor.go:281-321, BASELINE configs[2]).
        # All slices are local, so this takes the folded protocol: ONE
        # device fetch per query where r03 paid two phases.
        from pilosa_tpu.ops import bitplane as bpl

        cand = rng.integers(
            0, 2**32, size=(2048, bpl.WORDS_PER_SLICE), dtype=np.uint32
        )
        idx = holder.index("i")
        ft = idx.create_frame("t", cache_size=4096)
        view = ft.create_view_if_not_exists("standard")
        prime_fragment(
            view.create_fragment_if_not_exists(0), cand, bpl.pad_rows
        )

        tq = parse_string("TopN(Bitmap(rowID=0, frame=t), frame=t, n=100)")
        (warm,) = ex.execute("i", tq)  # compile + page
        assert len(warm) == 100

        def check_topn(res):
            pairs = res[0]
            assert len(pairs) == 100 and pairs[0].count >= pairs[-1].count

        t_p50, t_per_q, t_conc_p50 = measure_query(
            ex, "i", tq, check_topn, n_conc=8 if cpu_fb else 32, **trim
        )
        log(
            f"e2e executor TopN(n=100) folded single-fetch over 2048 rows:"
            f" sync p50 {t_p50*1e3:.2f} ms (incl. dispatch + fetch);"
            f" CONCURRENT(16) {t_per_q*1e3:.2f} ms/query throughput,"
            f" p50 latency under load {t_conc_p50*1e3:.2f} ms"
        )
        if not cpu_fb:
            _, t_64, _ = measure_query(
                ex, "i", tq, check_topn, n_serial=0, n_conc=128, threads=64
            )
            log(
                f"e2e executor TopN(n=100) CONCURRENT(64): {t_64*1e3:.2f}"
                f" ms/query throughput"
            )

        # --- tier 4: MULTI-SLICE TopN with a src bitmap -----------------
        # 64 slices x 128 ranked candidates, scored against a src row:
        # the fused scorer reads candidate and src rows straight from
        # the resident plane mirrors — one program + one fetch per
        # query where a per-slice protocol would pay 64 dispatches and
        # 64 src uploads (reference workload: Tanimoto similarity
        # search, docs/tutorials.md:333-342).
        MS_SLICES, MS_ROWS = 64, 128
        fm = idx.create_frame("m", cache_size=512)
        vm = fm.create_view_if_not_exists("standard")
        mrows = rng.integers(
            0, 2**32, size=(MS_SLICES, MS_ROWS, bpl.WORDS_PER_SLICE),
            dtype=np.uint32,
        )
        for s in range(MS_SLICES):
            prime_fragment(
                vm.create_fragment_if_not_exists(s), mrows[s], bpl.pad_rows
            )
        mq = parse_string("TopN(Bitmap(rowID=0, frame=m), frame=m, n=100)")
        (mwarm,) = ex.execute("i", mq)
        assert len(mwarm) == 100
        # Bit-exactness anchor: row 0's total must equal the host sum.
        want0 = int(
            sum(
                np.bitwise_count(mrows[s, 0] & mrows[s, 0]).sum()
                for s in range(MS_SLICES)
            )
        )
        got0 = {p.id: p.count for p in mwarm}[0]
        assert got0 == want0, f"multi-slice TopN exactness: {got0} != {want0}"

        def check_ms(res):
            pairs = res[0]
            assert len(pairs) == 100 and pairs[0].count >= pairs[-1].count

        m_p50, m_per_q, _ = measure_query(
            ex, "i", mq, check_ms, n_conc=8 if cpu_fb else 32, **trim
        )
        log(
            f"e2e executor TopN(src) over {MS_SLICES} slices x {MS_ROWS}"
            f" candidates (fused plane scorer): sync p50 {m_p50*1e3:.2f} ms"
            f" (incl. dispatch + fetch); CONCURRENT(16)"
            f" {m_per_q*1e3:.2f} ms/query throughput"
        )
        if not cpu_fb:
            # The prep cache leaves dispatch+fetch+selection per query;
            # more threads overlap the fetch RTTs further (same ladder
            # logic as the Count tier).
            _, m_32, _ = measure_query(
                ex, "i", mq, check_ms, n_serial=0, n_conc=96, threads=32
            )
            log(
                f"e2e executor TopN(src) CONCURRENT(32): {m_32*1e3:.2f}"
                f" ms/query throughput"
            )

        # Per-stage TopN(src) breakdown (prep / dispatch / plane fetch /
        # host winner-selection): the measurement groundwork for the
        # 5-7 ms warm residual (ROADMAP 5) — each warm query runs under
        # its own root trace and the topn.* span means land in the
        # artifact.
        stage_ms: dict[str, list] = {}
        for _ in range(5 if cpu_fb else 20):
            root = tr.start_trace("bench.topn")
            with root:
                ex.execute("i", mq)
            rec = tr.finish_root(root)
            for sp in (rec or {}).get("spans", []):
                if sp["name"].startswith("topn."):
                    stage_ms.setdefault(sp["name"], []).append(
                        sp["duration_ms"]
                    )
        topn_breakdown = {
            name: round(sorted(v)[len(v) // 2], 3)
            for name, v in sorted(stage_ms.items())
        }
        if topn_breakdown:
            log(
                "TopN(src) per-stage p50 ms: "
                + ", ".join(
                    f"{k.split('.', 1)[1]} {v}"
                    for k, v in topn_breakdown.items()
                )
            )
        ex.close()
        co.close()
        holder.close()
    coalesce_stats["total"] = co.snapshot()
    log(
        f"coalesce total: {coalesce_stats['total']['launches']} launches"
        f" for {coalesce_stats['total']['queries']} coalesced queries"
        f" (mean occupancy {coalesce_stats['total']['mean_occupancy']},"
        f" max {coalesce_stats['total']['max_occupancy']},"
        f" pad rows {coalesce_stats['total']['pad_rows']})"
    )
    return e2e_s, coalesce_stats, topn_breakdown


if __name__ == "__main__":
    main()
