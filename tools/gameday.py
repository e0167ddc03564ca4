"""Gameday: the everything-at-once soak (``make gameday``).

One seeded run composing every failure mode the stack claims to
survive, with a multi-tenant open-loop storm (tools/load_harness.py
machinery) driving it.  Legs, in timeline order:

  fairness    a tenant-configured node under a hot-tenant storm: the
              victim tenant's p99 stays within 2x its isolated
              baseline while the hot tenant sheds on quota (429 +
              X-Quota-* headers, visible in /debug/tenants), and
              goodput holds a floor;
  durability  3-node replica-3 cell, the third replica a CHILD
              PROCESS: quorum write storm, kill -9 the replica
              mid-storm (writes keep acking at quorum, hints queue),
              restart it (WAL recovery runs), hint replay drains to
              zero, and every coordinator answers byte-identically to
              the numpy oracle — zero lost acked writes;
  elasticity  2-node grid with standing subscriptions and a tier
              store: resize 2->3 under a live writer, a WINDOWED
              device-fault timeline (faults.py after-ms/until-ms)
              quarantines a device path mid-storm while answers stay
              byte-identical via host fallback, resize 3->2 back,
              demote cold slices below a forced disk budget and
              hydrate them back byte-identically, subscriptions
              converge to the pull oracle with bounded lag across
              both cutovers;
  gossip      an N-member SWIM set under seeded datagram loss
              converges full membership with no false-DOWN storm.

Under PILOSA_LOCK_CHECK=1 the runtime lock-order observations are
verified against the static lock graph at exit.  Prints ONE JSON
artifact line on stdout (or --artifact PATH); progress to stderr.
``--smoke`` scales every leg down for the blocking CI lane
(``make gameday-smoke``); the full run is the non-blocking soak.
CPU-only (virtual 8-device mesh): its node processes on one host would
each claim every chip, and partitioning chips among processes is
ROADMAP R5's work.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

# Virtual 8-device CPU mesh (same re-exec harness as multichip-smoke):
# the grid's M-device axis.  Must happen before jax imports.
if not os.environ.get("_GAMEDAY_REEXEC"):
    flags = os.environ.get("XLA_FLAGS", "")
    flags = " ".join(
        f for f in flags.split()
        if "xla_force_host_platform_device_count" not in f
    )
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count=8".strip()
    )
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["_GAMEDAY_REEXEC"] = "1"
    os.execv(sys.executable, [sys.executable] + sys.argv)

# Fault plans are installed per-leg in-process; an inherited env plan
# would silently compose with every leg's timeline.
os.environ.pop("PILOSA_FAULTS", None)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
TOOLS = os.path.dirname(os.path.abspath(__file__))
if TOOLS not in sys.path:
    sys.path.insert(0, TOOLS)

T0 = time.monotonic()
TIMELINE: list[dict] = []


def log(msg: str) -> None:
    print(f"[gameday +{time.monotonic() - T0:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


def mark(event: str, **detail) -> None:
    TIMELINE.append({"t_s": round(time.monotonic() - T0, 2),
                     "event": event, **detail})
    log(event + (f" {detail}" if detail else ""))


class GamedayFailure(AssertionError):
    pass


def require(cond, msg: str) -> None:
    if not cond:
        raise GamedayFailure(msg)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _free_udp_port() -> int:
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# ---------------------------------------------------------------------------
# child mode: the killable replica (durability leg)
# ---------------------------------------------------------------------------


def child(data_dir: str, host: str, ring_csv: str) -> int:
    """The victim replica as its own PROCESS so the parent can
    ``kill -9`` it.  Prints READY with the WAL recovery counters from
    open() — on restart they prove the acked tail was replayed."""
    from pilosa_tpu.cluster.topology import Cluster
    from pilosa_tpu.net.server import Server

    cluster = Cluster(replica_n=3)
    for h in ring_csv.split(","):
        cluster.add_node(h)
    cluster.nodes.sort(key=lambda n: n.host)
    s = Server(
        data_dir=data_dir,
        host=host,
        cluster=cluster,
        anti_entropy_interval=3600,
        polling_interval=3600,
        cache_flush_interval=3600,
        breaker_open_ms=300.0,
    )
    s.replication.replay_interval_s = 0.2
    s.open()
    snap = s.ingest.snapshot()
    print(f"READY {snap['replays']} {snap['replayedOps']}", flush=True)
    while True:  # serve until SIGKILL
        time.sleep(3600)


def _spawn_replica(data_dir: str, host: str, ring: list[str]):
    """(proc, replays, replayed_ops) once the child prints READY."""
    env = dict(os.environ)
    env.pop("PILOSA_FAULTS", None)  # parent-side fault plans stay local
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child",
         data_dir, host, ",".join(ring)],
        stdout=subprocess.PIPE, text=True, env=env, cwd=REPO,
    )
    deadline = time.time() + 120
    line = ""
    while time.time() < deadline:
        line = proc.stdout.readline()
        if line.startswith("READY"):
            _, replays, ops = line.split()
            return proc, int(replays), int(ops)
        if proc.poll() is not None:
            break
    proc.kill()
    raise GamedayFailure(f"replica child never came up: {line!r}")


# ---------------------------------------------------------------------------
# leg 1: multi-tenant fairness under storm
# ---------------------------------------------------------------------------


def leg_fairness(args) -> dict:
    import shutil
    import urllib.request

    import load_harness as lh

    mark("fairness: boot tenant-configured node")
    # Quota sized WELL under the hot tenant's storm share (8/9ths of
    # storm_qps below): the token bucket's burst capacity (= one
    # second of quota) must drain inside the measured window or the
    # storm ends before the first 429.
    hot_quota = 15.0 if args.smoke else 60.0
    tenants = [
        lh.TenantSpec("hot", 8.0, qps=hot_quota),
        lh.TenantSpec("victim", 1.0),
    ]
    td = tempfile.mkdtemp(prefix="gameday-fair-")
    ns = argparse.Namespace(
        point_concurrency=4, heavy_concurrency=2,
        write_concurrency=2, queue_depth=16,
    )
    server = lh.boot_server(os.path.join(td, "data"), ns, True,
                            tenants=tenants)
    try:
        mix = {"count": 1.0}
        lh.seed_corpus(server, slices=2, seed_values=False)
        workload = lh.Workload("i", mix, 2)
        for i in range(8):  # warm the compile path before measuring
            lh._do_request(server.host, *workload.request(i)[1:],
                           deadline_ms=30_000)

        dur = 2.0 if args.smoke else 4.0
        deadline_ms = 2000.0
        # Storm sized so the hot tenant's share (8/9ths) clearly
        # overruns its quota while total load stays inside the node's
        # GIL-bound capacity — fairness, not saturation, is on trial.
        storm_qps = 60.0 if args.smoke else 120.0
        # Unmeasured storm-shaped warmup: storm concurrency compiles
        # batched/coalesced execution paths the per-request warm loop
        # above never reaches — a first-compile stall must not land in
        # the measured window.
        lh.run_point(server.host, workload, storm_qps, 1.0, deadline_ms,
                     tenants=tenants)
        iso_qps = 10.0
        mark("fairness: victim isolated baseline", qps=iso_qps)
        iso = lh.run_point(server.host, workload, iso_qps, dur,
                           deadline_ms, tenants=[tenants[1]])
        p99_iso = iso["tenants"]["victim"]["p99_ms"]
        require(p99_iso is not None, "isolated baseline made no progress")

        # The QoS contract: the victim rides its own WFQ lane, so the
        # hot tenant's storm may at most double its p99.  The floor
        # keeps fast-baseline noise out of the ratio: an UNPROTECTED
        # victim behind a saturating neighbor queues for hundreds of
        # ms, so a 100 ms ceiling still proves isolation.  The victim's
        # p99 is its worst of ~20 samples, so one environmental stall
        # (GC, scheduler) can blow it — a bound miss gets ONE remeasure;
        # genuine unfairness reproduces, a stall does not.
        bound = 2.0 * max(p99_iso, 50.0)
        for attempt in (1, 2):
            mark("fairness: hot-tenant storm", qps=storm_qps,
                 attempt=attempt)
            storm = lh.run_point(server.host, workload, storm_qps, dur,
                                 deadline_ms, tenants=tenants)
            hot, victim = storm["tenants"]["hot"], storm["tenants"]["victim"]
            p99_storm = victim["p99_ms"]
            require(hot["shed"] > 0,
                    f"hot tenant never shed under storm: {hot}")
            require(victim["errors"] == 0, f"victim errored: {victim}")
            require(p99_storm is not None, "victim starved out entirely")
            if p99_storm <= bound:
                break
            log(f"fairness: victim p99 {p99_storm}ms over bound "
                f"{bound}ms on attempt {attempt}")
        require(
            p99_storm <= bound,
            f"victim p99 {p99_storm}ms > 2x isolated {p99_iso}ms "
            f"twice in a row",
        )
        floor = args.goodput_floor_qps
        require(
            storm["goodput_qps"] >= floor,
            f"goodput {storm['goodput_qps']} under floor {floor}",
        )
        # Quota shed must be VISIBLE: 429 + headers, /debug/tenants.
        req = urllib.request.Request(
            f"http://{server.host}/debug/tenants", method="GET"
        )
        with urllib.request.urlopen(req, timeout=10) as resp:
            table = json.loads(resp.read())
        require(
            table["tenants"]["hot"]["quotaShed"] >= 1,
            f"/debug/tenants shows no hot quota shed: {table}",
        )
        require(
            table["tenants"]["victim"]["admitted"] >= 1,
            "victim admits not visible in /debug/tenants",
        )
        mark("fairness: ok", victim_p99_iso_ms=p99_iso,
             victim_p99_storm_ms=p99_storm, hot_shed=hot["shed"])
        return {
            "victim_p99_isolated_ms": p99_iso,
            "victim_p99_storm_ms": p99_storm,
            "ratio": round(p99_storm / max(p99_iso, 1e-9), 2),
            "hot_shed": hot["shed"],
            "hot_shed_rate": hot["shed_rate"],
            "goodput_qps": storm["goodput_qps"],
            "debug_tenants_hot_quota_shed":
                table["tenants"]["hot"]["quotaShed"],
        }
    finally:
        server.close()
        shutil.rmtree(td, ignore_errors=True)


# ---------------------------------------------------------------------------
# leg 2: durability — kill -9 a replica mid-storm
# ---------------------------------------------------------------------------


def leg_durability(args) -> dict:
    import numpy as np

    from pilosa_tpu.cluster.topology import Cluster
    from pilosa_tpu.net import codec
    from pilosa_tpu.net.client import ClientError, InternalClient
    from pilosa_tpu.net.server import Server
    from pilosa_tpu.ops.bitplane import SLICE_WIDTH

    n_slices = 4
    storm_writes = 60 if args.smoke else 200
    tmp = tempfile.mkdtemp(prefix="gameday-dur-")

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
        s.replication.replay_interval_s = 0.2
        s.open()
        return s

    mark("durability: boot 3-node replica-3 cell (victim = subprocess)")
    s0, s1 = boot("n0"), boot("n1")
    victim_host = f"127.0.0.1:{_free_port()}"
    hosts = sorted([s0.host, s1.host, victim_host])
    for s in (s0, s1):
        for h in hosts:
            if s.cluster.node_by_host(h) is None:
                s.cluster.add_node(h)
        s.cluster.nodes.sort(key=lambda n: n.host)
    victim_dir = os.path.join(tmp, "victim")
    proc, _, _ = _spawn_replica(victim_dir, victim_host, hosts)
    victim_client = InternalClient(victim_host, timeout=10.0)
    try:
        for s in (s0, s1):
            s.holder.create_index_if_not_exists("i")
            s.holder.index("i").create_frame_if_not_exists("f")
        victim_client.create_index("i")
        victim_client.create_frame("i", "f")

        c0 = InternalClient(s0.host, timeout=10.0)
        for sl in range(n_slices):
            c0.execute_query(
                "i",
                f'SetBit(frame="f", rowID=1, columnID={sl * SLICE_WIDTH + sl})',
            )
        for s in (s0, s1):
            s._tick_max_slices()

        written: list[int] = []
        errors: list[str] = []

        def writer():
            cw = InternalClient(s0.host, timeout=10.0)
            for k in range(storm_writes):
                col = (k % n_slices) * SLICE_WIDTH + 100 + k // n_slices
                try:
                    cw.execute_query(
                        "i", f'SetBit(frame="f", rowID=3, columnID={col})'
                    )
                    written.append(col)
                except (ClientError, ConnectionError) as e:
                    errors.append(f"write {col}: {e}")
                    return
                time.sleep(0.005)

        t = threading.Thread(target=writer, daemon=True)
        t.start()
        time.sleep(0.1)

        mark("durability: kill -9 the replica mid-storm")
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=30)

        t.join(timeout=120)
        require(not errors,
                f"quorum writes errored with a replica down: {errors[:3]}")
        require(len(written) == storm_writes,
                f"writer confirmed {len(written)}/{storm_writes}")
        backlog = s0.replication.hints.backlog(victim_host) + (
            s1.replication.hints.backlog(victim_host)
        )
        require(backlog >= 1, "no hints queued for the dead replica")
        mark("durability: storm done at quorum", acked=len(written),
             hints=backlog)

        mark("durability: restart the replica (same port, same data)")
        proc, replays, replayed_ops = _spawn_replica(
            victim_dir, victim_host, hosts
        )
        require(replays >= 1,
                f"restart did not run WAL recovery (replays={replays})")
        mark("durability: WAL recovery ran", replays=replays,
             replayed_ops=replayed_ops)

        deadline = time.time() + 120
        while time.time() < deadline:
            if (s0.replication.hints.backlog(victim_host) == 0
                    and s1.replication.hints.backlog(victim_host) == 0):
                break
            time.sleep(0.2)
        require(
            s0.replication.hints.backlog(victim_host) == 0,
            "hint replay never drained",
        )
        mark("durability: hint replay drained to zero")

        # Byte-identical spot checks vs the numpy oracle, from EVERY
        # coordinator — including the restarted replica over HTTP.
        oracle = np.unique(np.asarray(written, dtype=np.int64))
        lost = None
        deadline = time.time() + 60
        while time.time() < deadline:
            lost = []
            for label, cl in (("n0", c0),
                              ("n1", InternalClient(s1.host, timeout=10.0)),
                              ("victim", victim_client)):
                rb = cl.execute_pql("i", 'Bitmap(frame="f", rowID=3)')
                got = np.asarray(codec.bitmap_to_json(rb)["bits"],
                                 dtype=np.int64)
                if not np.array_equal(got, oracle):
                    lost.append(f"{label}: {len(got)}/{len(oracle)} bits")
            if not lost:
                break
            time.sleep(0.5)
        require(not lost, f"acked writes lost after replay: {lost}")
        mark("durability: ok — zero lost acked writes",
             acked=len(oracle))
        return {
            "acked_writes": len(written),
            "hints_queued": backlog,
            "wal_replays": replays,
            "wal_replayed_ops": replayed_ops,
            "coordinators_byte_identical": 3,
        }
    finally:
        if proc.poll() is None:
            proc.kill()
        s0.close()
        s1.close()


# ---------------------------------------------------------------------------
# leg 3: elasticity — resize 2->3->2 + windowed device faults + tier
# ---------------------------------------------------------------------------


def leg_elasticity(args) -> dict:
    import shutil

    from pilosa_tpu.cluster.topology import Cluster
    from pilosa_tpu.net.client import ClientError, InternalClient
    from pilosa_tpu.net.server import Server
    from pilosa_tpu.ops.bitplane import SLICE_WIDTH
    from pilosa_tpu.pql.parser import Query
    from pilosa_tpu.testing import faults

    n_slices = 4
    n_subs = 8 if args.smoke else 16
    tmp = tempfile.mkdtemp(prefix="gameday-elastic-")
    store_url = os.path.join(tmp, "store")

    def boot(name, ring=()):
        cluster = Cluster(replica_n=1)
        for h in ring:
            cluster.add_node(h)
        s = Server(
            data_dir=os.path.join(tmp, name),
            cluster=cluster,
            anti_entropy_interval=3600,
            polling_interval=3600,
            cache_flush_interval=3600,
            rebalance_release_delay_ms=0.0,
            subscribe_refresh_ms=200.0,
            tier_store=store_url,
            tier_sweep_interval_s=3600,
            tenants=["gold:4", "bronze:1"],
        )
        s.open()
        return s

    mark("elasticity: boot 2-node grid (tier store + tenants + subs)")
    s0, s1 = boot("n0"), boot("n1")
    s2 = None
    stop = threading.Event()
    try:
        hosts2 = sorted([s0.host, s1.host])
        for s in (s0, s1):
            for h in hosts2:
                if s.cluster.node_by_host(h) is None:
                    s.cluster.add_node(h)
            s.cluster.nodes.sort(key=lambda n: n.host)
            s.holder.create_index_if_not_exists("i")
            s.holder.index("i").create_frame_if_not_exists("f")

        c0 = InternalClient(s0.host, timeout=15.0)
        for sl in range(n_slices):
            c0.execute_query(
                "i",
                f'SetBit(frame="f", rowID=0, columnID={sl * SLICE_WIDTH + sl})',
            )
        for s in (s0, s1):
            s._tick_max_slices()

        mgr = s0.subscribe
        subs = [
            mgr.register(
                "i", f'Subscribe(Count(Bitmap(rowID={r % 8}, frame="f")))'
            )
            for r in range(n_subs - 1)
        ]
        subs.append(mgr.register("i", 'Subscribe(TopN(frame="f", n=5))'))
        epoch0 = {sub.id: sub.epoch for sub in subs}

        confirmed: list[tuple[int, int]] = []
        reader_errs: list[str] = []

        def writer():
            cw = InternalClient(s0.host, timeout=10.0)
            k = 0
            while not stop.is_set():
                row = 1 + k % 7  # row 0 stays the reader's static truth
                col = (k % n_slices) * SLICE_WIDTH + 500 + k // n_slices
                try:
                    cw.execute_query(
                        "i",
                        f'SetBit(frame="f", rowID={row}, columnID={col})',
                    )
                    confirmed.append((row, col))
                except (ClientError, ConnectionError):
                    pass  # retried next loop; only confirmed count
                k += 1
                time.sleep(0.01)

        def reader():
            # Tenant-tagged reads during every cutover and fault
            # window: correctness only (row 0 is never written).
            cr = InternalClient(s0.host, timeout=15.0)
            misses = 0
            while not stop.is_set():
                try:
                    got = cr.execute_query(
                        "i", 'Count(Bitmap(frame="f", rowID=0))',
                        trace_headers={"X-Tenant": "bronze"},
                    )[0]
                    if got != n_slices:
                        # Confirm before failing: one stale answer in
                        # the middle of a routing cutover is a
                        # transient; an answer that's STILL wrong on
                        # the immediate re-read is lost data.
                        again = cr.execute_query(
                            "i", 'Count(Bitmap(frame="f", rowID=0))',
                            trace_headers={"X-Tenant": "bronze"},
                        )[0]
                        if again != n_slices:
                            reader_errs.append(
                                f"read {got} then {again} != {n_slices} "
                                f"at +{time.monotonic() - T0:.1f}s"
                            )
                            return
                    misses = 0
                except (ClientError, ConnectionError) as e:
                    misses += 1
                    if misses >= 8:
                        reader_errs.append(
                            f"reader at +{time.monotonic() - T0:.1f}s: {e}"
                        )
                        return
                time.sleep(0.03)

        threads = [threading.Thread(target=writer, daemon=True),
                   threading.Thread(target=reader, daemon=True)]
        for t in threads:
            t.start()
        time.sleep(0.5)

        def resize(hosts):
            status, data = c0._request(
                "POST", "/cluster/resize",
                body=json.dumps({"hosts": hosts}).encode(),
            )
            c0._check(status, data)
            deadline = time.time() + 180
            while time.time() < deadline:
                st, d = c0._request("GET", "/debug/rebalance")
                snap = json.loads(c0._check(st, d))
                if not snap.get("running") and snap.get("transition") is None:
                    return
                time.sleep(0.2)
            raise GamedayFailure(f"resize to {hosts} never completed")

        mark("elasticity: resize 2->3 under load")
        s2 = boot("n2", ring=hosts2)
        hosts3 = sorted(hosts2 + [s2.host])
        resize(hosts3)
        mark("elasticity: grow committed", hosts=len(hosts3))

        # WINDOWED device-fault timeline: quarantine opens 200 ms from
        # now, heals at 2200 ms — the storm rides through both edges.
        mark("elasticity: windowed device faults (after-ms/until-ms)")
        faults.install(
            "device.launch:kind=error,after-ms=200,until-ms=2200"
        )
        t_fault = time.monotonic()
        while time.monotonic() - t_fault < (1.5 if args.smoke else 3.0):
            got = c0.execute_pql("i", 'Count(Bitmap(frame="f", rowID=0))')
            require(got == n_slices,
                    f"answer diverged under device fault: {got}")
            time.sleep(0.02)
        quarantines = 0
        for s in (s0, s1, s2):
            snap = s.device_health.snapshot()
            quarantines += sum(
                p.get("quarantines", 0) for p in snap["paths"].values()
            )
        require(quarantines >= 1,
                "windowed device fault never quarantined a path")
        faults.clear()
        mark("elasticity: device quarantine observed, answers exact",
             quarantines=quarantines)

        mark("elasticity: resize 3->2 under load")
        resize(hosts2)
        mark("elasticity: shrink committed")

        time.sleep(0.5)
        stop.set()
        for t in threads:
            t.join(timeout=15)
        require(not reader_errs, f"reader failed: {reader_errs[:3]}")
        require(confirmed, "writer confirmed no writes across resizes")

        # Tier: archive, demote below a forced budget, hydrate back.
        mark("elasticity: tier demote/hydrate vs the object store")
        want_counts = [
            c0.execute_pql("i", f'Count(Bitmap(frame="f", rowID={r}))')
            for r in range(8)
        ]
        uploaded = s0.tier.upload_all()
        require(uploaded >= 1, "tier upload archived nothing")
        budget0 = s0.tier.disk_budget_bytes
        s0.tier.disk_budget_bytes = 1
        demoted = s0.tier.enforce_disk_budget()
        require(demoted >= 1, "budget sweep demoted nothing")
        after = [
            c0.execute_pql("i", f'Count(Bitmap(frame="f", rowID={r}))')
            for r in range(8)
        ]
        require(after == want_counts,
                f"post-demotion counts diverged: {after} != {want_counts}")
        s0.tier.disk_budget_bytes = budget0
        mark("elasticity: demote/hydrate byte-identical",
             uploaded=uploaded, demoted=demoted)

        # Subscriptions: converge to the pull oracle, bounded lag,
        # and the cutovers re-stamped epochs.
        require(mgr.flush(timeout=60.0), "pending deltas never drained")
        deadline = time.time() + 90
        stale = subs
        while time.time() < deadline and stale:
            nxt = []
            for sub in stale:
                want = s0.executor.execute("i", Query(calls=[sub.inner]))[0]
                if sub.value != want:
                    nxt.append(sub)
            stale = nxt
            if stale:
                time.sleep(0.2)
        require(not stale,
                f"{len(stale)} subscriptions never converged")
        flips = sum(
            1 for sub in subs if sub.epoch > epoch0[sub.id]
        )
        require(flips >= 1, "no subscription saw a topology epoch move")
        status, data = c0._request("GET", "/debug/subscriptions")
        dbg = json.loads(c0._check(status, data))
        lag = dbg["lagMs"]
        require(lag["samples"] > 0, "no notification batches measured")
        require(
            lag["p99"] is not None and lag["p99"] < args.sub_lag_bound_ms,
            f"subscription lag unbounded: {lag}",
        )
        mark("elasticity: subscriptions converged", subs=len(subs),
             lag_p99_ms=lag["p99"], epoch_flips=flips)
        return {
            "confirmed_writes": len(confirmed),
            "resizes": 2,
            "device_quarantines": quarantines,
            "tier_uploaded": uploaded,
            "tier_demoted": demoted,
            "subscriptions": len(subs),
            "sub_lag_p99_ms": lag["p99"],
            "sub_epoch_flips": flips,
        }
    finally:
        stop.set()
        faults.clear()
        for s in (s0, s1, s2):
            if s is not None:
                s.close()
        shutil.rmtree(tmp, ignore_errors=True)


# ---------------------------------------------------------------------------
# leg 4: gossip under datagram loss
# ---------------------------------------------------------------------------


def leg_gossip(args) -> dict:
    from pilosa_tpu.cluster.gossip import GossipNodeSet
    from pilosa_tpu.testing import faults

    n = 4 if args.smoke else 6
    loss = 0.20
    interval, suspect = 0.05, 0.6
    mark("gossip: member set under seeded datagram loss",
         members=n, loss=loss)
    faults.install(f"gossip.send:prob={loss},seed={args.seed},mode=drop")
    nodes: dict[str, GossipNodeSet] = {}
    try:
        seed_addr = ""
        for i in range(n):
            port = _free_udp_port()
            ns = GossipNodeSet(
                host=f"127.0.0.1:{9000 + i}",
                seed=seed_addr,
                gossip_interval=interval,
                suspect_after=suspect,
            )
            ns.bind = ("127.0.0.1", port)
            ns.advertise = ("127.0.0.1", port)
            ns.open()
            if not seed_addr:
                seed_addr = f"127.0.0.1:{port}"
            nodes[ns.host] = ns

        want = set(nodes)
        deadline = time.time() + 30.0
        while time.time() < deadline:
            if all(set(ns.nodes()) == want for ns in nodes.values()):
                break
            time.sleep(0.1)
        require(
            all(set(ns.nodes()) == want for ns in nodes.values()),
            f"membership never converged under {loss:.0%} loss",
        )
        # No false-DOWN storm over a couple of suspect windows.
        t_end = time.time() + 2 * suspect
        while time.time() < t_end:
            for h, ns in nodes.items():
                downs = [
                    m for m, st in ns.member_states().items()
                    if st == "DOWN" and m in nodes
                ]
                require(
                    not downs,
                    f"false-DOWN storm: {h} marked {downs} DOWN",
                )
            time.sleep(0.1)
        plan = faults.active()
        dropped = sum(r.hits for r in plan.rules) if plan else 0
        require(dropped >= 1, "the loss rule never fired")
        mark("gossip: converged, no false-DOWN", datagrams_dropped=dropped)
        return {"members": n, "loss": loss, "datagrams_dropped": dropped}
    finally:
        faults.clear()
        for ns in nodes.values():
            ns.close()


# ---------------------------------------------------------------------------


def main() -> int:
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        return child(sys.argv[2], sys.argv[3], sys.argv[4])

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="scaled-down blocking variant (gameday-smoke)")
    ap.add_argument("--seed", type=int, default=42,
                    help="seed for every stochastic leg (gossip loss)")
    ap.add_argument("--goodput-floor-qps", type=float, default=5.0)
    ap.add_argument("--sub-lag-bound-ms", type=float, default=20_000.0)
    ap.add_argument("--artifact", default="-",
                    help="artifact path ('-' = stdout)")
    args = ap.parse_args()

    import jax

    log(f"backend={jax.default_backend()} devices={len(jax.devices())} "
        f"seed={args.seed} smoke={args.smoke}")

    legs: dict[str, dict] = {}
    ok = True
    failure = ""
    try:
        legs["fairness"] = leg_fairness(args)
        legs["durability"] = leg_durability(args)
        legs["elasticity"] = leg_elasticity(args)
        legs["gossip"] = leg_gossip(args)
    except GamedayFailure as e:
        ok = False
        failure = str(e)
        log(f"FAIL: {e}")

    lock_check = "skipped"
    if os.environ.get("PILOSA_LOCK_CHECK"):
        from pilosa_tpu.analyze import runtime as lock_check_mod

        problems = lock_check_mod.verify()
        log(lock_check_mod.report().splitlines()[0])
        if problems:
            for p in problems:
                log(f"lock-check DISAGREEMENT: {p}")
            lock_check = "FAILED"
            ok = False
        else:
            lock_check = "ok"
            log("lock-check ok: runtime order consistent with static graph")

    artifact = {
        "tool": "gameday",
        "seed": args.seed,
        "smoke": args.smoke,
        "ok": ok,
        "legs": legs,
        "timeline": TIMELINE,
        "lock_check": lock_check,
        "wall_s": round(time.monotonic() - T0, 1),
    }
    if failure:
        artifact["failure"] = failure
    line = json.dumps(artifact)
    if args.artifact == "-":
        print(line)
    else:
        with open(args.artifact, "w") as f:
            f.write(line + "\n")
        log(f"artifact written to {args.artifact}")
        print(line)
    if ok:
        log("gameday OK: all legs green")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
