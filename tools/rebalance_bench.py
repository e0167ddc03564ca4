"""Rebalance bench tier — live 2->3 grow under sustained load, with
every node in its OWN process (per-node GIL isolation, like a real
deployment — an in-process 3-node harness would charge the migration
for scheduler contention production doesn't have).

Boots two `pilosa-tpu server` subprocesses over a seeded corpus,
measures steady-state read latency under a concurrent writer, then
live-grows to a third subprocess node with the migration bandwidth-
throttled, sampling read latency DURING the background copy.  Emits
ONE JSON line:

  steady_p50_ms / steady_p99_ms    (reads, writer running)
  during_p50_ms / during_p99_ms    (reads overlapping the migration)
  p99_ratio                        (during / steady — the SLO figure)
  migration_s, slices_moved
  writes_confirmed, writes_lost    (must be 0)
  results_identical                (bitmap before == after cutover)

Run standalone or embedded by bench.py as the ``rebalance`` tier.
CPU-only (every node is started with JAX_PLATFORMS=cpu): several node
processes on one host would each claim every chip, and partitioning
chips among processes is ROADMAP R5's work.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

sys.path.insert(0, ".")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

from pilosa_tpu.net import codec  # noqa: E402
from pilosa_tpu.net.client import ClientError, InternalClient  # noqa: E402
from pilosa_tpu.ops.bitplane import SLICE_WIDTH  # noqa: E402

N_SLICES = int(os.environ.get("REBALANCE_BENCH_SLICES", "16"))
BITS_PER_SLICE = int(os.environ.get("REBALANCE_BENCH_BITS", "2000"))
THROTTLE_MBPS = float(os.environ.get("REBALANCE_BENCH_THROTTLE_MBPS", "4"))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def free_tcp_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def boot_node(tmp: str, name: str, host: str, ring: list[str]):
    """One real node in its own process."""
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PILOSA_DATA_DIR=f"{tmp}/{name}",
        PILOSA_HOST=host,
        PILOSA_CLUSTER_HOSTS=",".join(ring),
        PILOSA_CLUSTER_POLLING_INTERVAL="1",
        PILOSA_ANTI_ENTROPY_INTERVAL="3600",
        PILOSA_CLUSTER_REBALANCE_THROTTLE_MBPS=str(THROTTLE_MBPS),
        PILOSA_CLUSTER_REBALANCE_RELEASE_DELAY_MS="0",
        # The nodes share the default compile cache (one fixed path in
        # the checkout): the JOINING node deserializes the fused
        # programs instead of paying a cold XLA compile on the first
        # query routed at it post-flip.
        PILOSA_TPU_PREWARM="true",
    )
    return subprocess.Popen(
        [sys.executable, "-m", "pilosa_tpu.cli", "server"],
        env=env,
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
    )


def wait_ready(host: str, timeout: float = 90.0) -> None:
    client = InternalClient(host, timeout=2.0)
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            status, data = client._request("GET", "/version")
            client._check(status, data)
            return
        except Exception:  # noqa: BLE001 — still booting
            time.sleep(0.2)
    raise SystemExit(f"FAIL: node {host} never became ready")


def wait_prewarm(host: str, timeout: float = 120.0) -> None:
    """Block until the node's compiled-program count is non-zero and
    stable across two reads — its background prewarm has landed."""
    client = InternalClient(host, timeout=5.0)
    last = -1
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            status, data = client._request("GET", "/metrics")
            body = client._check(status, data).decode()
            n = 0
            for line in body.splitlines():
                if line.startswith("pilosa_exec_programCache_entries "):
                    n = int(float(line.rsplit(" ", 1)[1]))
            if n > 0 and n == last:
                return
            last = n
        except Exception:  # noqa: BLE001 — scrape may race the boot
            pass
        time.sleep(1.0)
    log(f"warning: prewarm on {host} never stabilized; proceeding")


def pql_count(client, row=1):
    return client.execute_pql("i", f'Count(Bitmap(frame="f", rowID={row}))')


def pcts(ms):
    if not ms:
        return 0.0, 0.0
    arr = sorted(ms)
    return (
        arr[len(arr) // 2],
        arr[min(len(arr) - 1, int(len(arr) * 0.99))],
    )


def main() -> int:
    tmp = tempfile.mkdtemp(prefix="rebalance-bench-")
    ports = [free_tcp_port() for _ in range(3)]
    hosts2 = sorted(f"127.0.0.1:{p}" for p in ports[:2])
    host3 = f"127.0.0.1:{ports[2]}"
    hosts3 = sorted(hosts2 + [host3])
    procs = []
    stop = threading.Event()
    try:
        for i, h in enumerate(hosts2):
            procs.append(boot_node(tmp, f"n{i}", h, hosts2))
        for h in hosts2:
            wait_ready(h)
        log(f"2-node ring up: {hosts2}")

        c0 = InternalClient(hosts2[0], timeout=30.0)
        # Static cluster type: no schema broadcaster — create on each
        # member (the joining third node gets the schema pushed by the
        # rebalance coordinator).
        for h in hosts2:
            ch = InternalClient(h, timeout=10.0)
            try:
                ch.create_index("i")
            except ClientError:
                pass
            try:
                ch.create_frame("i", "f")
            except ClientError:
                pass
        rng = np.random.default_rng(11)
        log(f"seeding {N_SLICES} slices x {BITS_PER_SLICE} bits")
        for sl in range(N_SLICES):
            cols = rng.choice(SLICE_WIDTH, size=BITS_PER_SLICE, replace=False)
            c0.import_bits(
                "i", "f", sl,
                (np.ones(len(cols), np.int64),
                 cols.astype(np.int64) + sl * SLICE_WIDTH),
            )
        # Let the 1 s max-slice polling tick propagate the slice range.
        want = N_SLICES * BITS_PER_SLICE
        deadline = time.time() + 30
        while time.time() < deadline and pql_count(c0) != want:
            time.sleep(0.3)
        assert pql_count(c0) == want, "corpus never converged"
        rb = c0.execute_pql("i", 'Bitmap(frame="f", rowID=1)')
        baseline = codec.bitmap_to_json(rb)["bits"]
        log(f"corpus ready: count={want}")

        # The concurrent writer runs through BOTH measurement windows,
        # so the p99 ratio isolates the MIGRATION's interference.
        written: list[int] = []

        def writer():
            cw = InternalClient(hosts2[0], timeout=10.0)
            k = 0
            while not stop.is_set():
                col = (k % N_SLICES) * SLICE_WIDTH + SLICE_WIDTH - 1 - k // N_SLICES
                try:
                    cw.execute_query(
                        "i", f'SetBit(frame="f", rowID=7, columnID={col})'
                    )
                    written.append(col)
                except (ClientError, ConnectionError):
                    pass
                k += 1
                time.sleep(0.005)

        wt = threading.Thread(target=writer, daemon=True)
        wt.start()

        # Warm the query path (compiles, batch caches) before the
        # steady window — cold-start cost is the cold_restart tier's
        # number, not this one's.
        for _ in range(10):
            pql_count(c0)

        steady: list[float] = []
        t_end = time.time() + 3.0
        while time.time() < t_end:
            t0 = time.perf_counter()
            pql_count(c0)
            steady.append((time.perf_counter() - t0) * 1e3)
        steady_p50, steady_p99 = pcts(steady)
        log(f"steady (with writer): p50 {steady_p50:.2f} ms "
            f"p99 {steady_p99:.2f} ms ({len(steady)} samples)")

        # The joining node: configured with the OLD ring (it is not a
        # member until the transition admits it).
        procs.append(boot_node(tmp, "n2", host3, hosts2))
        wait_ready(host3)
        # Let its background prewarm land before admitting it (the
        # operator workflow docs/administration.md prescribes): the
        # first post-flip query must not pay a cold XLA compile.
        wait_prewarm(host3)

        during: list[float] = []

        def sampler():
            while not stop.is_set():
                t0 = time.perf_counter()
                try:
                    pql_count(c0)
                    during.append((time.perf_counter() - t0) * 1e3)
                except (ClientError, ConnectionError):
                    pass  # begin/commit epoch windows
                time.sleep(0.002)

        st_thread = threading.Thread(target=sampler, daemon=True)
        st_thread.start()

        t0 = time.time()
        status, data = c0._request(
            "POST", "/cluster/resize",
            body=json.dumps({"hosts": hosts3}).encode(),
        )
        c0._check(status, data)
        deadline = time.time() + 600
        while time.time() < deadline:
            st, d = c0._request("GET", "/debug/rebalance")
            snap = json.loads(c0._check(st, d))
            if not snap.get("running") and snap.get("transition") is None:
                break
            if not snap.get("running") and (
                (snap.get("coordinator") or {}).get("error")
            ):
                raise SystemExit(f"FAIL: migration error: {snap}")
            time.sleep(0.1)
        else:
            raise SystemExit("FAIL: migration did not complete")
        migration_s = time.time() - t0
        time.sleep(0.3)
        stop.set()
        wt.join(timeout=10)
        st_thread.join(timeout=10)

        during_p50, during_p99 = pcts(during)
        after = codec.bitmap_to_json(
            c0.execute_pql("i", 'Bitmap(frame="f", rowID=1)')
        )["bits"]
        got7 = codec.bitmap_to_json(
            c0.execute_pql("i", 'Bitmap(frame="f", rowID=7)')
        )["bits"]
        lost = len(set(written)) - len(set(got7) & set(written))
        moved = 0
        for sl in range(N_SLICES):
            nodes = c0.fragment_nodes("i", sl)
            if nodes and nodes[0]["host"] == host3:
                moved += 1
        out = {
            "steady_p50_ms": round(steady_p50, 3),
            "steady_p99_ms": round(steady_p99, 3),
            "during_p50_ms": round(during_p50, 3),
            "during_p99_ms": round(during_p99, 3),
            "p99_ratio": round(during_p99 / steady_p99, 2) if steady_p99 else 0,
            "migration_s": round(migration_s, 2),
            "slices_moved": moved,
            "during_samples": len(during),
            "writes_confirmed": len(set(written)),
            "writes_lost": lost,
            "results_identical": after == baseline,
            "throttle_mbps": THROTTLE_MBPS,
            "slices": N_SLICES,
            "isolation": "process-per-node",
        }
        log(
            f"migration {migration_s:.1f}s, {moved} slices moved; "
            f"reads during: p50 {during_p50:.2f} ms p99 {during_p99:.2f} ms "
            f"({out['p99_ratio']}x steady); writes lost: {lost}"
        )
        print(json.dumps(out))
        if lost or not out["results_identical"]:
            raise SystemExit("FAIL: correctness violated under migration")
        return 0
    finally:
        stop.set()
        for p in procs:
            try:
                p.send_signal(signal.SIGTERM)
            except OSError:
                pass
        for p in procs:
            try:
                p.wait(timeout=15)
            except subprocess.TimeoutExpired:
                p.kill()


if __name__ == "__main__":
    raise SystemExit(main())
