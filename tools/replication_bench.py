"""Replication bench tier (bench.py ``replication``): write latency at
each consistency level and the hint-replay drain rate.

Boots a 3-node, replica-3 in-process cluster on the CPU backend and
measures

* write p50/p99 (ms) of single SetBit requests through one coordinator
  at consistency one / quorum / all — the cost of each ack level on a
  healthy cluster;
* hint replay drain rate: kill a replica, push a burst of quorum
  writes (each queuing a hint), restart it, and time the
  breaker-triggered replay from first backlog to checksum convergence
  — hints/s and the end-to-end recovery seconds.

One JSON line on stdout; progress on stderr.  Scale knobs:
``BENCH_REPLICATION_WRITES`` (per level, default 80) and
``BENCH_REPLICATION_HINTS`` (burst size, default 150).
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

N_SLICES = 4


def log(msg: str) -> None:
    print(f"[replication] {msg}", file=sys.stderr)


def pctl(xs, p):
    xs = sorted(xs)
    return round(xs[min(len(xs) - 1, int(len(xs) * p))] * 1000.0, 3)


def main() -> int:
    from pilosa_tpu.cluster.topology import Cluster
    from pilosa_tpu.net.client import InternalClient
    from pilosa_tpu.net.server import Server
    from pilosa_tpu.ops.bitplane import SLICE_WIDTH

    writes_per_level = int(os.environ.get("BENCH_REPLICATION_WRITES", "80"))
    hint_burst = int(os.environ.get("BENCH_REPLICATION_HINTS", "150"))
    tmp = tempfile.mkdtemp(prefix="replication-bench-")

    def boot(name, host="127.0.0.1:0", ring=()):
        cluster = Cluster(replica_n=3)
        for h in ring:
            cluster.add_node(h)
        s = Server(
            data_dir=os.path.join(tmp, name),
            host=host,
            cluster=cluster,
            anti_entropy_interval=3600,
            polling_interval=3600,
            cache_flush_interval=3600,
            breaker_open_ms=300.0,
        )
        s.replication.replay_interval_s = 0.1
        s.open()
        return s

    servers = [boot(f"n{i}") for i in range(3)]
    hosts = sorted(s.host for s in servers)
    for s in servers:
        for h in hosts:
            if s.cluster.node_by_host(h) is None:
                s.cluster.add_node(h)
        s.cluster.nodes.sort(key=lambda n: n.host)
    for s in servers:
        s.holder.create_index_if_not_exists("i")
        s.holder.index("i").create_frame_if_not_exists("f")

    s0 = servers[0]
    c0 = InternalClient(s0.host, timeout=30.0)
    for sl in range(N_SLICES):
        c0.execute_query(
            "i", f'SetBit(frame="f", rowID=1, columnID={sl * SLICE_WIDTH + sl})'
        )
    for s in servers:
        s._tick_max_slices()

    # -- write latency per consistency level ---------------------------
    out: dict = {"writes": {}, "replicas": 3, "nodes": 3}
    col = 10_000
    for level in ("one", "quorum", "all"):
        lat = []
        for _ in range(writes_per_level):
            col += 1
            q = (
                f'SetBit(frame="f", rowID=2, '
                f'columnID={(col % N_SLICES) * SLICE_WIDTH + col})'
            )
            t0 = time.perf_counter()
            c0.execute_query(
                "i", q, trace_headers={"X-Write-Consistency": level}
            )
            lat.append(time.perf_counter() - t0)
        out["writes"][level] = {
            "n": len(lat),
            "p50_ms": pctl(lat, 0.50),
            "p99_ms": pctl(lat, 0.99),
        }
        log(
            f"write {level}: p50 {out['writes'][level]['p50_ms']} ms, "
            f"p99 {out['writes'][level]['p99_ms']} ms"
        )

    # -- hint replay drain rate ----------------------------------------
    victim = servers[2]
    victim_host = victim.host
    victim.close()
    t0 = time.perf_counter()
    for k in range(hint_burst):
        col += 1
        c0.execute_query(
            "i",
            f'SetBit(frame="f", rowID=3, '
            f'columnID={(k % N_SLICES) * SLICE_WIDTH + 50_000 + k})',
        )
    burst_s = time.perf_counter() - t0
    backlog = s0.replication.hints.backlog(victim_host)
    log(f"burst: {hint_burst} quorum writes in {burst_s:.2f}s with one "
        f"replica dead ({backlog} hints queued)")

    victim = boot("n2", host=victim_host, ring=hosts)
    servers[2] = victim

    def checksums(server, sl):
        return server.rebalance.delta_action(
            {"index": "i", "slice": sl, "action": "checksum"}
        )["checksums"]

    t0 = time.perf_counter()
    deadline = time.time() + 120
    while time.time() < deadline:
        if s0.replication.hints.backlog(victim_host) == 0 and all(
            checksums(s0, sl) == checksums(victim, sl)
            for sl in range(N_SLICES)
        ):
            break
        time.sleep(0.05)
    else:
        log("FAIL: hint replay never converged")
        for s in servers:
            s.close()
        return 1
    drain_s = time.perf_counter() - t0
    # The replayed counter lands AFTER the pass's verify leg; poll
    # briefly so the artifact records the real figure.
    replayed = 0
    deadline = time.time() + 10
    while time.time() < deadline:
        replayed = (
            s0.replication.hints.snapshot()["targets"]
            .get(victim_host, {})
            .get("replayed", 0)
        )
        if replayed >= backlog:
            break
        time.sleep(0.05)
    out["hint_replay"] = {
        "queued": backlog,
        "replayed": replayed,
        "drain_s": round(drain_s, 3),
        "hints_per_s": round(replayed / drain_s, 1) if drain_s > 0 else 0.0,
        "converged": True,
    }
    log(
        f"hint replay: {replayed} hints drained in {drain_s:.2f}s "
        f"({out['hint_replay']['hints_per_s']}/s), checksums converged"
    )
    for s in servers:
        s.close()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
