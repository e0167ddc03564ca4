"""``degraded`` bench tier — device-fault tolerance figures.

Measures, on the virtual 8-device CPU mesh (re-exec harness shared with
mesh_bench/multichip_smoke):

* healthy-path Count throughput (Gcols/s) + p50/p99 per-query latency;
* the same storm with the accelerator QUARANTINED (persistent injected
  launch fault): host-fallback Gcols/s + p50/p99, every answer
  byte-checked against the healthy run, plus how many queries the
  quarantine threshold cost before the breaker engaged;
* watchdog trip recovery: one injected hang inside the collective
  dispatch — the tripped query's end-to-end latency IS the recovery
  time (bounded by ``launch-watchdog-ms``, not by the wedge).

Emits one JSON object on stdout; bench.py folds it into the artifact as
``degraded`` and bench-smoke asserts its shape.  Sizing via
``BENCH_DEGRADED_SLICES`` (default 16) and ``BENCH_DEGRADED_ITERS``
(default 30).
"""

from __future__ import annotations

import json
import os
import sys
import time

if not os.environ.get("_DEGRADED_BENCH_REEXEC"):
    flags = os.environ.get("XLA_FLAGS", "")
    flags = " ".join(
        f for f in flags.split()
        if "xla_force_host_platform_device_count" not in f
    )
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count=8".strip()
    )
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["_DEGRADED_BENCH_REEXEC"] = "1"
    os.execv(sys.executable, [sys.executable] + sys.argv)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

N_SLICES = int(os.environ.get("BENCH_DEGRADED_SLICES", "16"))
ITERS = int(os.environ.get("BENCH_DEGRADED_ITERS", "30"))
WATCHDOG_MS = 200.0


def log(msg: str) -> None:
    print(f"[degraded] {msg}", file=sys.stderr, flush=True)


def pct(samples, p):
    if not samples:
        return None
    s = sorted(samples)
    k = min(len(s) - 1, max(0, int(round(p / 100.0 * (len(s) - 1)))))
    return round(s[k], 3)


def storm(ex, parse_string, q, iters):
    lat = []
    results = []
    for _ in range(iters):
        t0 = time.monotonic()
        (res,) = ex.execute("i", parse_string(q))
        lat.append((time.monotonic() - t0) * 1e3)
        results.append(int(res))
    return lat, results


def main() -> int:
    import tempfile

    import jax

    from pilosa_tpu.cluster.topology import new_cluster
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.device.health import DeviceHealth
    from pilosa_tpu.exec import Executor
    from pilosa_tpu.exec.coalesce import CoalesceScheduler
    from pilosa_tpu.ops.bitplane import SLICE_WIDTH
    from pilosa_tpu.pql.parser import parse_string
    from pilosa_tpu.testing import faults

    log(
        f"backend={jax.default_backend()} devices={len(jax.devices())} "
        f"slices={N_SLICES} iters={ITERS}"
    )
    rng = np.random.default_rng(11)
    tmp = tempfile.mkdtemp(prefix="degraded-bench-")
    holder = Holder(os.path.join(tmp, "data"))
    holder.open()
    idx = holder.create_index("i")
    f = idx.create_frame("f")
    bits_per = 4096
    rows_l, cols_l = [], []
    for s in range(N_SLICES):
        for row in (1, 2):
            pos = rng.choice(SLICE_WIDTH, size=bits_per, replace=False)
            rows_l.append(np.full(bits_per, row, dtype=np.int64))
            cols_l.append(s * SLICE_WIDTH + pos.astype(np.int64))
    f.import_bulk(np.concatenate(rows_l), np.concatenate(cols_l))
    cluster = new_cluster(1)
    host = cluster.nodes[0].host
    q = "Count(Intersect(Bitmap(rowID=1, frame=f), Bitmap(rowID=2, frame=f)))"
    cols_per_query = N_SLICES * SLICE_WIDTH

    def gcols(lat_ms):
        total_s = sum(lat_ms) / 1e3
        return round(cols_per_query * len(lat_ms) / total_s / 1e9, 3)

    out: dict = {"slices": N_SLICES, "iters": ITERS}

    # --- healthy ---------------------------------------------------------
    threshold = 3
    dh = DeviceHealth(
        quarantine_threshold=threshold, open_ms=3600_000, watchdog_ms=0
    )
    co = CoalesceScheduler(health=dh)
    ex = Executor(holder, host=host, cluster=cluster, coalescer=co, device_health=dh)
    try:
        lat, res = storm(ex, parse_string, q, ITERS)
        want = res[0]
        out["healthy"] = {
            "gcols_s": gcols(lat),
            "p50_ms": pct(lat, 50),
            "p99_ms": pct(lat, 99),
        }
        log(f"healthy: {out['healthy']}")

        # --- degraded (quarantined -> host fallback) ---------------------
        faults.install("device.launch:mode=error")
        qn = 0
        while not dh.degraded() and qn < threshold * 4:
            (r,) = ex.execute("i", parse_string(q))
            qn += 1
            assert int(r) == want, "wrong answer while quarantining"
        out["quarantine_queries"] = qn
        out["quarantine_threshold"] = threshold
        lat, res = storm(ex, parse_string, q, ITERS)
        out["byte_identical"] = all(r == want for r in res)
        out["degraded"] = {
            "gcols_s": gcols(lat),
            "p50_ms": pct(lat, 50),
            "p99_ms": pct(lat, 99),
        }
        log(
            f"degraded (host fallback): {out['degraded']} after "
            f"{qn} queries to quarantine"
        )
        faults.clear()
    finally:
        ex.close()
        co.close()
        dh.close()

    # --- watchdog trip recovery -----------------------------------------
    dh = DeviceHealth(
        quarantine_threshold=3, open_ms=3600_000, watchdog_ms=WATCHDOG_MS
    )
    co = CoalesceScheduler(health=dh)
    ex = Executor(holder, host=host, cluster=cluster, coalescer=co, device_health=dh)
    try:
        # Warm the per-slice fallback program so the recovery figure
        # measures the watchdog, not a cold compile.
        ex.execute("i", parse_string(q))
        faults.install(
            "device.launch:kind=hang,path=collective,times=1,"
            f"delay-ms={WATCHDOG_MS * 4:.0f}"
        )
        t0 = time.monotonic()
        (r,) = ex.execute("i", parse_string(q))
        trip_ms = (time.monotonic() - t0) * 1e3
        faults.clear()
        assert int(r) == want, "wrong answer through the watchdog trip"
        out["watchdog"] = {
            "watchdog_ms": WATCHDOG_MS,
            "trip_recovery_ms": round(trip_ms, 3),
            "trips": dh.snapshot()["watchdogTrips"],
        }
        log(f"watchdog: {out['watchdog']}")
    finally:
        faults.clear()
        ex.close()
        co.close()
        dh.close()
        holder.close()

    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
