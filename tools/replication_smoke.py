"""CI smoke for quorum replication (``make replication-smoke``): a
3-node, replica-3 cluster takes a sustained quorum write storm while
one replica is KILLED mid-storm, then restarted — the pass asserts

* every storm write succeeded at consistency=quorum (2 of 3 acks)
  while the replica was down, with hints queued for it;
* after restart the hint replay (breaker-triggered, no operator action)
  drains to ZERO backlog and the restarted replica's fragments
  checksum-agree with the survivors WITHOUT an anti-entropy tick
  (the loop is disabled at a 3600 s interval);
* zero lost writes: every confirmed column is present in the restarted
  replica's LOCAL fragments;
* a sub-quorum write (consistency=all against the dead replica) fails
  loudly.

Deterministic CPU pass, in-process servers; BLOCKING in CI
(.github/workflows/check.yml) like resize-smoke.
"""

from __future__ import annotations

import os
import sys
import tempfile
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

N_SLICES = 4
STORM_WRITES = 120


def main() -> int:
    from pilosa_tpu.cluster.topology import Cluster
    from pilosa_tpu.net.client import ClientError, InternalClient
    from pilosa_tpu.net.server import Server
    from pilosa_tpu.ops.bitplane import SLICE_WIDTH

    tmp = tempfile.mkdtemp(prefix="replication-smoke-")

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
    c0 = InternalClient(s0.host, timeout=10.0)
    for sl in range(N_SLICES):
        c0.execute_query(
            "i", f'SetBit(frame="f", rowID=1, columnID={sl * SLICE_WIDTH + sl})'
        )
    for s in servers:
        s._tick_max_slices()

    victim = servers[2]
    victim_host = victim.host
    stop = threading.Event()
    written: list[int] = []
    errors: list[str] = []

    def writer():
        cw = InternalClient(s0.host, timeout=10.0)
        for k in range(STORM_WRITES):
            if stop.is_set():
                return
            col = (k % N_SLICES) * SLICE_WIDTH + 100 + k // N_SLICES
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

    # KILL the replica mid-storm.
    victim.close()
    print(f"[replication-smoke] killed replica {victim_host} mid-storm",
          file=sys.stderr)

    t.join(timeout=60.0)
    stop.set()
    if errors:
        print(f"FAIL: quorum writes errored with one replica down: "
              f"{errors[:3]}", file=sys.stderr)
        return 1
    if len(written) != STORM_WRITES:
        print(f"FAIL: writer confirmed {len(written)}/{STORM_WRITES}",
              file=sys.stderr)
        return 1
    backlog = s0.replication.hints.backlog(victim_host)
    if backlog < 1:
        print("FAIL: no hints queued for the dead replica", file=sys.stderr)
        return 1
    print(f"[replication-smoke] {len(written)} quorum writes ok, "
          f"{backlog} hints queued", file=sys.stderr)

    # Sub-quorum must fail loudly while the replica is down.
    try:
        c0.execute_query(
            "i",
            f'SetBit(frame="f", rowID=6, columnID={SLICE_WIDTH + 42})',
            trace_headers={"X-Write-Consistency": "all"},
        )
        print("FAIL: consistency=all write succeeded with a dead replica",
              file=sys.stderr)
        return 1
    except (ClientError, ConnectionError) as e:
        if "need 3" not in str(e):
            print(f"FAIL: sub-quorum error did not name the ack math: {e}",
                  file=sys.stderr)
            return 1

    # RESTART: the breaker transition triggers replay; converge.
    victim = boot("n2", host=victim_host, ring=hosts)
    servers[2] = victim

    def checksums(server, sl):
        return server.rebalance.delta_action(
            {"index": "i", "slice": sl, "action": "checksum"}
        )["checksums"]

    deadline = time.time() + 60
    converged = False
    while time.time() < deadline:
        if s0.replication.hints.backlog(victim_host) == 0 and all(
            checksums(s0, sl) == checksums(victim, sl)
            for sl in range(N_SLICES)
        ):
            converged = True
            break
        time.sleep(0.2)
    if not converged:
        print(
            "FAIL: no convergence after restart: backlog="
            f"{s0.replication.hints.backlog(victim_host)}",
            file=sys.stderr,
        )
        return 1

    # Zero lost writes: every confirmed column is in the restarted
    # replica's LOCAL fragments.
    view = victim.holder.index("i").frame("f").view("standard")
    have = 0
    for sl in range(N_SLICES):
        frag = view.fragment(sl)
        if frag is not None:
            have += frag._count_of.get(3, 0)
    expect = len(set(written))
    for s in servers:
        s.close()
    if have != expect:
        print(f"FAIL: lost writes: replica has {have} of {expect}",
              file=sys.stderr)
        return 1
    print(
        f"OK: {expect} storm writes at quorum with a mid-storm replica "
        f"kill; hint replay converged checksums on restart with zero "
        "lost writes and no anti-entropy tick"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
