"""Open-loop sustained-load harness: storm a node, sweep offered load,
record goodput-vs-offered-load and max-sustained-QPS-at-p99-SLO.

Closed-loop benchmarks (a fixed worker pool waiting for each answer)
self-throttle under overload and hide the collapse this harness exists
to measure.  Here request START times are fixed by the offered rate
alone — completions never gate arrivals (no coordinated omission), so
when the node saturates, the backlog grows exactly like real traffic
and the curve shows what admission control does about it:

* with shedding, excess load answers 429 in microseconds and goodput
  (answers within their deadline) plateaus at node capacity;
* without it, every request is admitted, queues past its deadline, and
  goodput collapses into 504s-after-burned-work.

Traffic is a weighted mix of cost classes (point Count, heavy
TopN/Range, import writes) against a seeded corpus.  Each request
carries ``X-Deadline-Ms``; a response only counts toward goodput when
it arrives 200 within that budget.

Modes:
  --self-boot        boot an in-process server (CPU or current backend),
                     seed it, sweep, tear down.  --compare runs the
                     sweep twice — admission ON then OFF — into one
                     artifact.
  --host HOST:PORT   storm an external node (expects index/frame/field
                     already seeded unless --seed).

Prints ONE JSON artifact line on stdout (or --artifact PATH); all
progress goes to stderr.  Used by ``make load-smoke``
(tools/load_smoke.py) and ``tools/gameday.py``.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# workload mix
# ---------------------------------------------------------------------------


class Workload:
    """Weighted request templates over the seeded corpus.  Deterministic
    per-index choice (no shared RNG lock on the hot path)."""

    def __init__(self, index: str, mix: dict[str, float], slices: int):
        self.index = index
        self.slices = max(1, slices)
        kinds = [(k, w) for k, w in mix.items() if w > 0]
        total = sum(w for _, w in kinds)
        # Weighted 1000-step wheel, deterministically shuffled so a
        # short run still interleaves every kind (requests index the
        # wheel sequentially).
        import random

        self.wheel: list[str] = []
        for kind, w in kinds:
            self.wheel.extend([kind] * max(1, int(round(w / total * 1000))))
        random.Random(0).shuffle(self.wheel)

    def request(self, i: int) -> tuple[str, str, str, bytes]:
        """(kind, method, path, body) for the i-th request."""
        kind = self.wheel[i % len(self.wheel)]
        idx = self.index
        if kind == "count":
            row = i % 2
            return (
                kind,
                "POST",
                f"/index/{idx}/query",
                f'Count(Bitmap(frame="f", rowID={row}))'.encode(),
            )
        if kind == "topn":
            return kind, "POST", f"/index/{idx}/query", b'TopN(frame="f", n=5)'
        if kind == "range":
            return (
                kind,
                "POST",
                f"/index/{idx}/query",
                f'Count(Range(frame="f", v > {i % 7}))'.encode(),
            )
        if kind == "import":
            col = (i * 97) % (self.slices * (1 << 20))
            body = json.dumps(
                {
                    "index": idx,
                    "frame": "f",
                    "field": "v",
                    "slice": col >> 20,
                    "columnIDs": [col],
                    "values": [i % 100],
                }
            ).encode()
            return kind, "POST", "/import-value", body
        raise ValueError(f"unknown kind {kind!r}")


class TenantSpec:
    """One ``name:weight[:qps[:bytes_per_s]]`` entry from ``--tenants``.
    Weight picks the share of storm traffic this tenant generates; the
    optional quotas are forwarded to the self-booted server config so
    the harness can demonstrate 429-on-quota without a config file."""

    __slots__ = ("name", "weight", "qps", "bytes_per_s")

    def __init__(self, name: str, weight: float, qps: float = 0.0,
                 bytes_per_s: float = 0.0):
        self.name = name
        self.weight = weight
        self.qps = qps
        self.bytes_per_s = bytes_per_s

    @classmethod
    def parse(cls, spec: str) -> "TenantSpec":
        parts = spec.split(":")
        if not parts[0] or len(parts) > 4:
            raise ValueError(f"bad tenant spec {spec!r} "
                             "(want name:weight[:qps[:bytes_per_s]])")
        weight = float(parts[1]) if len(parts) > 1 and parts[1] else 1.0
        qps = float(parts[2]) if len(parts) > 2 and parts[2] else 0.0
        bps = float(parts[3]) if len(parts) > 3 and parts[3] else 0.0
        if weight <= 0:
            raise ValueError(f"tenant {parts[0]!r}: weight must be > 0")
        return cls(parts[0], weight, qps, bps)

    def server_spec(self) -> str:
        """The ``[net] tenants`` entry equivalent of this spec."""
        s = f"{self.name}:{max(1, int(round(self.weight)))}"
        if self.qps or self.bytes_per_s:
            s += f":{self.qps:g}"
        if self.bytes_per_s:
            s += f":{self.bytes_per_s:g}"
        return s


def parse_tenants(spec: str) -> list[TenantSpec]:
    return [TenantSpec.parse(p) for p in spec.split(",") if p.strip()]


def tenant_wheel(tenants: list[TenantSpec], steps: int = 1000) -> list[str]:
    """Deterministic weighted wheel of tenant names (same trick as the
    workload mix wheel: request i is charged to wheel[i % len])."""
    import random

    total = sum(t.weight for t in tenants)
    wheel: list[str] = []
    for t in tenants:
        wheel.extend([t.name] * max(1, int(round(t.weight / total * steps))))
    random.Random(1).shuffle(wheel)
    return wheel


_conn_local = threading.local()


def _do_request(
    host: str, method: str, path: str, body: bytes, deadline_ms: float,
    tenant: str = "",
) -> tuple[int, bytes]:
    """One HTTP request on this thread's keep-alive connection
    (reconnect once on a dead socket)."""
    timeout = deadline_ms / 1000.0 * 3 + 1.0
    headers = {"X-Deadline-Ms": str(int(deadline_ms))}
    if tenant:
        headers["X-Tenant"] = tenant
    for attempt in (0, 1):
        conn = getattr(_conn_local, "conn", None)
        if conn is None or getattr(_conn_local, "host", None) != host:
            conn = http.client.HTTPConnection(host, timeout=timeout)
            _conn_local.conn, _conn_local.host = conn, host
        try:
            conn.request(method, path, body=body, headers=headers)
            resp = conn.getresponse()
            return resp.status, resp.read()
        except (OSError, http.client.HTTPException):
            conn.close()
            _conn_local.conn = None
            if attempt:
                raise
    raise RuntimeError("unreachable")


# ---------------------------------------------------------------------------
# measurement
# ---------------------------------------------------------------------------


def probe_capacity(
    host: str, workload: Workload, seconds: float, threads: int,
    deadline_ms: float,
) -> float:
    """Closed-loop capacity estimate: ``threads`` workers hammering
    point queries; capacity = completed / wall time."""
    stop = time.monotonic() + seconds
    done = [0] * threads

    def worker(w: int) -> None:
        i = 0
        while time.monotonic() < stop:
            try:
                status, _ = _do_request(
                    host, *workload.request(i)[1:], deadline_ms=deadline_ms
                )
                if status == 200:
                    done[w] += 1
            except Exception:  # noqa: BLE001 — probe is best-effort
                pass
            i += 1

    ts = [threading.Thread(target=worker, args=(w,)) for w in range(threads)]
    t0 = time.monotonic()
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    wall = time.monotonic() - t0
    return sum(done) / max(wall, 1e-9)


def run_point(
    host: str,
    workload: Workload,
    offered_qps: float,
    duration_s: float,
    deadline_ms: float,
    tenants: list[TenantSpec] | None = None,
) -> dict:
    """One open-loop point: fire ``offered_qps * duration_s`` requests
    at fixed schedule times; latency is measured from the SCHEDULED
    start (coordinated-omission-free).  With ``tenants``, each request
    carries ``X-Tenant`` sampled from the weighted tenant wheel and
    stats are additionally broken out per tenant."""
    n = max(1, int(offered_qps * duration_s))
    pool = ThreadPoolExecutor(
        max_workers=min(512, max(16, int(offered_qps * deadline_ms / 1000.0 * 2)))
    )
    lock = threading.Lock()
    stats = {
        "ok_within_deadline": 0,
        "ok_late": 0,
        "shed": 0,
        "deadline_504": 0,
        "errors": 0,
    }
    ok_latencies: list[float] = []
    wheel = tenant_wheel(tenants) if tenants else []
    by_tenant: dict[str, dict] = {
        t.name: {"sent": 0, "ok": 0, "shed": 0, "errors": 0, "lat": []}
        for t in (tenants or [])
    }

    def fire(i: int, t_sched: float) -> None:
        kind, method, path, body = workload.request(i)
        tenant = wheel[i % len(wheel)] if wheel else ""
        try:
            status, _ = _do_request(
                host, method, path, body, deadline_ms, tenant=tenant
            )
        except Exception:  # noqa: BLE001 — client-side failure
            with lock:
                stats["errors"] += 1
                if tenant:
                    by_tenant[tenant]["errors"] += 1
            return
        lat_ms = (time.monotonic() - t_sched) * 1000.0
        with lock:
            ts = by_tenant.get(tenant)
            if ts is not None:
                ts["sent"] += 1
            if status == 200:
                if lat_ms <= deadline_ms:
                    stats["ok_within_deadline"] += 1
                    ok_latencies.append(lat_ms)
                else:
                    stats["ok_late"] += 1
                if ts is not None:
                    ts["ok"] += 1
                    ts["lat"].append(lat_ms)
            elif status == 429:
                stats["shed"] += 1
                if ts is not None:
                    ts["shed"] += 1
            elif status == 504:
                stats["deadline_504"] += 1
            else:
                stats["errors"] += 1
                if ts is not None:
                    ts["errors"] += 1

    t0 = time.monotonic()
    for i in range(n):
        target = t0 + i / offered_qps
        now = time.monotonic()
        if target > now:
            time.sleep(target - now)
        # Open loop: submit at the scheduled instant regardless of how
        # many earlier requests are still in flight.
        pool.submit(fire, i, target)
    pool.shutdown(wait=True)
    wall = time.monotonic() - t0

    ok_latencies.sort()

    def pct(p: float) -> float | None:
        if not ok_latencies:
            return None
        return round(ok_latencies[min(len(ok_latencies) - 1,
                                      int(p * len(ok_latencies)))], 2)

    sent = n
    out = {
        "offered_qps": round(offered_qps, 1),
        "duration_s": round(wall, 2),
        "sent": sent,
        **stats,
        "goodput_qps": round(stats["ok_within_deadline"] / max(wall, 1e-9), 1),
        "shed_rate": round(stats["shed"] / sent, 4),
        "p50_ms": pct(0.50),
        "p99_ms": pct(0.99),
    }
    if by_tenant:
        tenants_out = {}
        for name, ts in by_tenant.items():
            lat = sorted(ts.pop("lat"))
            ts["p99_ms"] = (
                round(lat[min(len(lat) - 1, int(0.99 * len(lat)))], 2)
                if lat else None
            )
            ts["shed_rate"] = round(ts["shed"] / max(ts["sent"], 1), 4)
            tenants_out[name] = ts
        out["tenants"] = tenants_out
    return out


def run_sweep(
    host: str,
    workload: Workload,
    qps_points: list[float],
    duration_s: float,
    deadline_ms: float,
    slo_ms: float,
    tenants: list[TenantSpec] | None = None,
) -> dict:
    points = []
    for qps in qps_points:
        pt = run_point(host, workload, qps, duration_s, deadline_ms,
                       tenants=tenants)
        log(
            f"  offered {pt['offered_qps']:>8} qps -> goodput "
            f"{pt['goodput_qps']:>8} qps, p99 {pt['p99_ms']} ms, "
            f"shed {pt['shed']}, 504 {pt['deadline_504']}, "
            f"errors {pt['errors']}"
        )
        points.append(pt)
    sustained = [
        p["goodput_qps"]
        for p in points
        if p["p99_ms"] is not None and p["p99_ms"] <= slo_ms
    ]
    return {
        "deadline_ms": deadline_ms,
        "slo_ms": slo_ms,
        "points": points,
        "max_sustained_qps_at_p99_slo": max(sustained) if sustained else 0.0,
    }


# ---------------------------------------------------------------------------
# self-boot
# ---------------------------------------------------------------------------


def boot_server(data_dir: str, args, admission_on: bool,
                tenants: list[TenantSpec] | None = None):
    from pilosa_tpu.net.server import Server
    from pilosa_tpu.obs.stats import ExpvarStatsClient

    s = Server(
        data_dir=data_dir,
        host="127.0.0.1:0",
        anti_entropy_interval=3600,
        polling_interval=3600,
        cache_flush_interval=3600,
        stats=ExpvarStatsClient(),
        prewarm=False,
        admission=admission_on,
        admission_point_concurrency=args.point_concurrency,
        admission_heavy_concurrency=args.heavy_concurrency,
        admission_write_concurrency=args.write_concurrency,
        admission_queue_depth=args.queue_depth,
        # Configure the storm tenants server-side so bare X-Tenant tags
        # resolve (unconfigured tags fall back to the default tenant).
        tenants=[t.server_spec() for t in (tenants or [])],
    )
    s.open()
    return s


def seed_corpus(server, slices: int, seed_values: bool) -> None:
    """Small standard corpus: 2 rows over ``slices`` slices plus (for
    the range mix) a BSI field with a few values per slice."""
    import numpy as np

    holder = server.holder
    holder.create_index_if_not_exists("i")
    idx = holder.index("i")
    idx.create_frame_if_not_exists("f", range_enabled=seed_values)
    f = holder.frame("i", "f")
    cols_per = 256
    for sl in range(slices):
        base = sl << 20
        cols = np.arange(cols_per, dtype=np.int64) * 64 + base
        rows = np.zeros(cols_per, dtype=np.int64)
        f.import_bulk(
            np.concatenate([rows, rows + 1]), np.concatenate([cols, cols])
        )
    if seed_values:
        f.create_field("v", 0, 1000)
        for sl in range(slices):
            base = sl << 20
            cols = np.arange(cols_per, dtype=np.int64) * 64 + base
            vals = (cols % 97).astype(np.int64)
            f.import_value("v", cols, vals)
    idx.set_remote_max_slice(slices - 1)


def self_boot_sweep(args, admission_on: bool) -> dict:
    import shutil

    td = tempfile.mkdtemp(prefix="load-harness-")
    tenants = parse_tenants(args.tenants) if args.tenants else None
    server = boot_server(os.path.join(td, "data"), args, admission_on,
                         tenants=tenants)
    try:
        mix = parse_mix(args.mix)
        seed_corpus(server, args.slices, seed_values="range" in mix or "import" in mix)
        workload = Workload("i", mix, args.slices)
        # Warm the query path (compiles, mirrors) before measuring.
        for i in range(8):
            _do_request(
                server.host, *workload.request(i)[1:], deadline_ms=30_000
            )
        if args.qps:
            qps_points = [float(q) for q in args.qps.split(",")]
            capacity = None
        else:
            capacity = probe_capacity(
                server.host, workload, args.probe_s, threads=16,
                deadline_ms=30_000,
            )
            log(f"capacity probe ({'on' if admission_on else 'off'}): "
                f"{capacity:.0f} qps closed-loop")
            qps_points = [
                max(1.0, capacity * m)
                for m in (0.5, 1.0, 1.5, 2.0, 3.0)
            ]
        out = run_sweep(
            server.host, workload, qps_points, args.duration,
            args.deadline_ms, args.slo_ms, tenants=tenants,
        )
        out["admission"] = admission_on
        if capacity is not None:
            out["capacity_qps_closed_loop"] = round(capacity, 1)
        if admission_on and server.admission is not None:
            out["admission_snapshot"] = server.admission.snapshot()
        if tenants is not None:
            out["tenants_snapshot"] = server.tenants.snapshot()
        return out
    finally:
        server.close()
        shutil.rmtree(td, ignore_errors=True)


def parse_mix(spec: str) -> dict[str, float]:
    out: dict[str, float] = {}
    for part in spec.split(","):
        k, _, w = part.partition("=")
        out[k.strip()] = float(w or 1.0)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--host", default="", help="external node to storm")
    ap.add_argument("--self-boot", action="store_true")
    ap.add_argument(
        "--compare", action="store_true",
        help="self-boot twice: admission on, then off (baseline)",
    )
    ap.add_argument("--index", default="i")
    ap.add_argument("--slices", type=int, default=4)
    ap.add_argument(
        "--mix", default="count=0.55,topn=0.2,range=0.15,import=0.1",
        help="kind=weight[,kind=weight...] over count/topn/range/import",
    )
    ap.add_argument(
        "--qps", default="",
        help="comma-separated offered-load points; empty = probe "
        "capacity and sweep 0.5/1/1.5/2/3x",
    )
    ap.add_argument("--duration", type=float, default=5.0,
                    help="seconds per offered-load point")
    ap.add_argument("--probe-s", type=float, default=3.0)
    ap.add_argument("--deadline-ms", type=float, default=500.0)
    ap.add_argument("--slo-ms", type=float, default=250.0,
                    help="p99 SLO for the max-sustained-QPS figure")
    ap.add_argument(
        "--tenants", default="",
        help="name:weight[:qps[:bytes_per_s]][,name:weight...] — tag "
        "each request with X-Tenant sampled by weight; self-boot also "
        "configures the tenants (weights + quotas) server-side",
    )
    ap.add_argument("--seed", action="store_true",
                    help="with --host: seed the corpus first")
    ap.add_argument("--point-concurrency", type=int, default=32)
    ap.add_argument("--heavy-concurrency", type=int, default=8)
    ap.add_argument("--write-concurrency", type=int, default=16)
    ap.add_argument("--queue-depth", type=int, default=64)
    ap.add_argument("--artifact", default="-",
                    help="artifact path ('-' = stdout)")
    args = ap.parse_args()

    artifact: dict = {"tool": "load_harness", "mix": args.mix}
    if args.tenants:
        artifact["tenant_specs"] = args.tenants
    if args.self_boot or args.compare:
        log("=== sweep with admission control ===")
        artifact["admission_on"] = self_boot_sweep(args, admission_on=True)
        if args.compare:
            log("=== baseline sweep, admission OFF ===")
            artifact["admission_off"] = self_boot_sweep(
                args, admission_on=False
            )
        artifact["max_sustained_qps_at_p99_slo"] = artifact["admission_on"][
            "max_sustained_qps_at_p99_slo"
        ]
    elif args.host:
        from pilosa_tpu.net.client import InternalClient  # noqa: F401 — import check

        mix = parse_mix(args.mix)
        workload = Workload(args.index, mix, args.slices)
        qps_points = [float(q) for q in args.qps.split(",") if q] or None
        if qps_points is None:
            cap = probe_capacity(args.host, workload, args.probe_s, 16,
                                 deadline_ms=30_000)
            log(f"capacity probe: {cap:.0f} qps")
            qps_points = [max(1.0, cap * m) for m in (0.5, 1.0, 1.5, 2.0, 3.0)]
        artifact["sweep"] = run_sweep(
            args.host, workload, qps_points, args.duration,
            args.deadline_ms, args.slo_ms,
            tenants=parse_tenants(args.tenants) if args.tenants else None,
        )
        artifact["max_sustained_qps_at_p99_slo"] = artifact["sweep"][
            "max_sustained_qps_at_p99_slo"
        ]
    else:
        ap.error("need --self-boot or --host")

    line = json.dumps(artifact)
    if args.artifact == "-":
        print(line)
    else:
        with open(args.artifact, "w") as f:
            f.write(line + "\n")
        log(f"artifact written to {args.artifact}")
        print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
