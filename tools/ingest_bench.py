"""Ingest bench tier (bench.py ``ingest``): what durability costs and
what delta-scatter saves.

Three measurement legs on the CPU backend, one JSON line on stdout:

* **Durable write throughput, group commit on / off / WAL off** — an
  8-thread acked SetBit storm through the full handler/executor path.
  ``group_on`` batches concurrent writers into one fsync per window
  (2 ms); ``group_off`` forces a commit per append (window 0, batch 1);
  ``wal_off`` is the pre-WAL baseline.  Reports acks/s, WAL MB/s, the
  fsync count vs ack count (the group-commit amplification win), mean
  group size, and write p50/p99 — bench-smoke asserts fsyncs << acks
  with the write p99 bounded by the commit window.

* **Read p99 under a 50/50 read/write storm** — writers park on
  group-commit futures (GIL released), so the fsync wait must stay OFF
  the read path.  A control leg runs the identical storm against a
  disjoint frame (same WAL/fsync load, zero read-path interplay) to
  carry the in-process thread-scheduling noise in the denominator:
  bench-smoke asserts the mixed read p99 is <= 1.5x the control p99.

* **Mirror re-stage bytes, scatter on / off** — a point-write + device
  read loop against a dense fragment.  Scatter ON applies each delta as
  one tiny fused launch and keeps the HBM mirror; OFF invalidates and
  re-uploads the full plane per round.  bench-smoke asserts the byte
  ratio is >= 100x.

Scale knobs: ``BENCH_INGEST_WRITES`` (per thread, default 250),
``BENCH_INGEST_THREADS`` (default 8), ``BENCH_INGEST_READS`` (default
400), ``BENCH_INGEST_RESTAGE_ROUNDS`` (default 150).
"""

from __future__ import annotations

import json
import os
import shutil
import statistics
import sys
import tempfile
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def log(msg: str) -> None:
    print(f"[ingest] {msg}", file=sys.stderr)


def pctl(xs, p):
    xs = sorted(xs)
    return round(xs[min(len(xs) - 1, int(len(xs) * p))] * 1000.0, 3)


def boot(data_dir, **kw):
    from pilosa_tpu.net.server import Server

    srv = Server(data_dir=data_dir, host="127.0.0.1:0",
                 anti_entropy_interval=3600, polling_interval=3600, **kw)
    srv.open()
    srv.holder.create_index_if_not_exists("i")
    srv.holder.index("i").create_frame_if_not_exists("f")
    return srv


def write_storm(srv, threads: int, writes: int, row_base: int = 0):
    """Acked SetBit storm through the handler; returns (latencies_s,
    acks, wall_s)."""
    from pilosa_tpu.net.handler import Request

    lat: list[list[float]] = [[] for _ in range(threads)]
    errs: list[str] = []

    def run(t: int) -> None:
        for k in range(writes):
            col = k * threads + t
            q = f'SetBit(frame="f", rowID={row_base + t}, columnID={col})'
            t0 = time.perf_counter()
            r = srv.handler.dispatch(
                Request("POST", "/index/i/query", body=q.encode())
            )
            lat[t].append(time.perf_counter() - t0)
            if r.status != 200:
                errs.append(f"{r.status} {r.body!r}")
                return

    ts = [threading.Thread(target=run, args=(t,)) for t in range(threads)]
    t0 = time.perf_counter()
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    wall = time.perf_counter() - t0
    if errs:
        raise RuntimeError(f"storm errors: {errs[:3]}")
    flat = [x for per in lat for x in per]
    return flat, len(flat), wall


def durability_arm(tmp: str, name: str, **server_kw) -> dict:
    threads = int(os.environ.get("BENCH_INGEST_THREADS", "8"))
    writes = int(os.environ.get("BENCH_INGEST_WRITES", "250"))
    srv = boot(os.path.join(tmp, name), **server_kw)
    try:
        lat, acks, wall = write_storm(srv, threads, writes)
        snap = srv.ingest.snapshot() if srv.ingest is not None else {}
    finally:
        srv.close()
    fsyncs = int(snap.get("totalFsyncs", 0))
    appends = int(snap.get("totalAppends", 0))
    wal_bytes = sum(
        w.get("walBytesWritten", 0) for w in snap.get("writers", [])
    )
    arm = {
        "acks": acks,
        "wall_s": round(wall, 3),
        "acks_per_s": round(acks / wall, 1) if wall > 0 else 0.0,
        "wal_mb_per_s": round(wal_bytes / wall / 1e6, 3) if wall > 0 else 0.0,
        "fsyncs": fsyncs,
        "appends": appends,
        "mean_group_size": round(appends / fsyncs, 1) if fsyncs else 0.0,
        "write_p50_ms": pctl(lat, 0.50),
        "write_p99_ms": pctl(lat, 0.99),
    }
    log(f"{name}: {arm['acks_per_s']} acks/s, {fsyncs} fsyncs for "
        f"{acks} acks (group {arm['mean_group_size']}), "
        f"write p99 {arm['write_p99_ms']} ms")
    return arm


def read_storm_arm(tmp: str) -> dict:
    """Read p99 under a 50/50 acked write storm.

    Three legs: ``read_only`` (quiet process, reported for scale),
    ``control`` (the same paced acked-writer storm against a DISJOINT
    frame — full WAL, group-commit, and fsync load, zero read-path
    interplay), and ``mixed`` (the storm hits the very fragment being
    read).  The asserted ratio is mixed/control: in-process writer
    threads cost a reader GIL/scheduler time no matter what they write,
    so the control leg carries that noise in the denominator and the
    ratio isolates what durable ingest itself — fragment-lock holds,
    fsync waits, pending scatter applies — adds to the read tail.

    The reads cycle over 8 distinct rowIDs, overflowing the executor's
    4-entry batch cache, so every leg measures FULL query execution.  A
    fixed query would let the control leg serve version-validated cache
    hits (its fragment never changes) while the mixed leg's writes
    invalidate every read — a cache-semantics asymmetry that predates
    the WAL and would swamp the ingest signal."""
    from pilosa_tpu.net.handler import Request

    reads = int(os.environ.get("BENCH_INGEST_READS", "400"))
    srv = boot(os.path.join(tmp, "mixed"))
    try:
        # The control storm writes frame "g": same index, same slice,
        # different fragment — the reads below never touch it.
        srv.holder.index("i").create_frame_if_not_exists("g")
        # Seed the read rows so the Counts have real work.
        for row in range(1, 9):
            for col in range(0, 2048, 7):
                srv.handler.dispatch(Request(
                    "POST", "/index/i/query",
                    body=f'SetBit(frame="f", rowID={row}, '
                         f'columnID={col + row})'.encode(),
                ))
        srv.ingest.wait_durable()

        def read_leg() -> list[float]:
            # Warmup absorbs one-time costs (plane upload, program
            # compiles, the first scatter apply) that would otherwise
            # land as a p99 outlier in whichever leg runs first.
            lat = []
            for i in range(reads + 20):
                q = f'Count(Bitmap(frame="f", rowID={1 + i % 8}))'
                t0 = time.perf_counter()
                r = srv.handler.dispatch(Request(
                    "POST", "/index/i/query", body=q.encode(),
                ))
                if i >= 20:
                    lat.append(time.perf_counter() - t0)
                assert r.status == 200, r.body
            return lat

        def stormed_leg(frame: str) -> list[float]:
            stop = threading.Event()

            def writer(t: int) -> None:
                # Open-loop 50/50 mix: writes paced to roughly the
                # read rate rather than a saturating spin — the point
                # is whether durable-write work leaks into the read
                # path, not raw GIL contention between saturated
                # dispatch loops.
                k = 0
                while not stop.is_set():
                    col = 4096 + k * 4 + t
                    srv.handler.dispatch(Request(
                        "POST", "/index/i/query",
                        body=f'SetBit(frame="{frame}", rowID=9, '
                             f'columnID={col})'.encode(),
                    ))
                    k += 1
                    time.sleep(0.002)

            ws = [threading.Thread(target=writer, args=(t,), daemon=True)
                  for t in range(2)]
            for w in ws:
                w.start()
            try:
                # Let the first group-commit tick land before measuring:
                # the committer's first pending-scatter apply for this
                # plane shape compiles its program while holding the
                # fragment lock, a one-time stall no steady state pays.
                time.sleep(0.05)
                return read_leg()
            finally:
                stop.set()
                for w in ws:
                    w.join(timeout=30)

        ro = read_leg()
        # Alternate the legs and take the median per-leg p99: a p99
        # estimated from a few hundred samples rides on its 2-3 worst
        # draws, and one scheduler/GC hiccup landing in either leg
        # would swing the asserted ratio by 2x.
        controls, mixeds = [], []
        for _ in range(3):
            controls.append(pctl(stormed_leg("g"), 0.99))
            mixeds.append(pctl(stormed_leg("f"), 0.99))
    finally:
        srv.close()
    p99_ro = pctl(ro, 0.99)
    p99_control = statistics.median(controls)
    p99_mixed = statistics.median(mixeds)
    arm = {
        "reads": reads,
        "read_only_p99_ms": p99_ro,
        "control_p99_ms": p99_control,
        "mixed_p99_ms": p99_mixed,
        "p99_ratio": (
            round(p99_mixed / p99_control, 2) if p99_control > 0 else 0.0
        ),
    }
    log(f"read p99: quiet {p99_ro} ms, control storm {p99_control} ms, "
        f"50/50 storm {p99_mixed} ms -> ratio {arm['p99_ratio']}x")
    return arm


def restage_arm(tmp: str) -> dict:
    """Mirror re-stage bytes across a point-write + device-read loop,
    scatter on vs off (fragment-level: the mirror mechanics live below
    the server)."""
    from pilosa_tpu.core.fragment import Fragment
    from pilosa_tpu.device import pool
    from pilosa_tpu.ingest import scatter as ingest_scatter

    rounds = int(os.environ.get("BENCH_INGEST_RESTAGE_ROUNDS", "150"))
    out = {}
    was = ingest_scatter.ENABLED
    try:
        for name, enabled in (("scatter_on", True), ("scatter_off", False)):
            ingest_scatter.ENABLED = enabled
            frag = Fragment(os.path.join(tmp, name, "0"),
                            "i", "f", "standard", 0)
            frag.open()
            try:
                for row in range(4):
                    for col in range(0, 4096, 3):
                        frag.set_bit(row, col)
                frag.device_row(0)  # initial upload
                before = pool().restage_bytes()
                t0 = time.perf_counter()
                for k in range(rounds):
                    frag.set_bit(k % 4, 5000 + k)
                    frag.device_row(k % 4)  # forces mirror sync
                wall = time.perf_counter() - t0
                delta = pool().restage_bytes() - before
            finally:
                frag.close()
            out[name] = {
                "rounds": rounds,
                "restage_bytes": int(delta),
                "wall_s": round(wall, 3),
            }
            log(f"{name}: {delta} re-staged bytes over {rounds} rounds "
                f"({out[name]['wall_s']}s)")
    finally:
        ingest_scatter.ENABLED = was
    on = max(1, out["scatter_on"]["restage_bytes"])
    out["bytes_ratio"] = round(out["scatter_off"]["restage_bytes"] / on, 1)
    out["scatter"] = dict(ingest_scatter.counters())
    log(f"re-stage bytes ratio (off/on): {out['bytes_ratio']}x")
    return out


def main() -> int:
    # Mixed-workload tail control: CPython's default 5 ms GIL switch
    # interval lets one thread's bytecode stretch sit on the GIL for an
    # entire ~1 ms read's p99 budget; 0.5 ms bounds that hold with no
    # measurable throughput cost at bench scale.
    sys.setswitchinterval(0.0005)
    tmp = tempfile.mkdtemp(prefix="ingest-bench-")
    try:
        out: dict = {"write": {}}
        out["write"]["group_on"] = durability_arm(tmp, "group_on")
        out["write"]["group_off"] = durability_arm(
            tmp, "group_off",
            ingest_group_commit_ms=0.0, ingest_group_commit_max=1,
        )
        out["write"]["wal_off"] = durability_arm(
            tmp, "wal_off", ingest_wal=False,
        )
        out["read"] = read_storm_arm(tmp)
        out["restage"] = restage_arm(tmp)
        print(json.dumps(out))
        return 0
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
