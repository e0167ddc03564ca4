"""CI chaos smoke: two in-process nodes under PILOSA_FAULTS — one
erroring and one delayed RPC leg — and a fan-out query must still
answer correctly.

Not a benchmark and not the full chaos suite (tests/test_resilience.py)
— a wiring check that the resilience layer actually engages end to end:
the injected transport error is retried, the injected delay is absorbed
within the deadline, the answer is exact, and the fault rules really
fired.  Run via ``make chaos-smoke``; wired into CI as a non-blocking
step.
"""

from __future__ import annotations

import os
import socket
import sys
import tempfile

# CPU backend before jax/pilosa import (same bootstrap as
# tests/conftest.py: a chaos soak never needs the chip), and the repo
# root on sys.path so `make chaos-smoke` works uninstalled.
os.environ["JAX_PLATFORMS"] = "cpu"
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def main() -> int:
    p0, p1 = _free_port(), _free_port()
    h0, h1 = f"127.0.0.1:{p0}", f"127.0.0.1:{p1}"
    # One ERRORING leg: the first query RPC to node 1 dies on send (the
    # retry policy must absorb it).  One DELAYED leg: node 1's next
    # query receive stalls 150 ms (well inside the deadline).
    os.environ["PILOSA_FAULTS"] = (
        f"rpc.send:host={h1},path=/index/*/query,nth=1,mode=error;"
        f"rpc.recv:host={h1},path=/index/*/query,nth=1,mode=delay,delay-ms=150"
    )

    import jax

    jax.config.update("jax_platforms", "cpu")

    from pilosa_tpu.cluster.topology import Cluster
    from pilosa_tpu.net.client import InternalClient
    from pilosa_tpu.net.server import Server
    from pilosa_tpu.ops.bitplane import SLICE_WIDTH
    from pilosa_tpu.testing import faults

    quiet = dict(
        anti_entropy_interval=3600,
        polling_interval=3600,
        cache_flush_interval=3600,
        retry_backoff_ms=20,
    )
    with tempfile.TemporaryDirectory() as td:

        def make(name: str, host: str) -> Server:
            cluster = Cluster(replica_n=1)
            s = Server(
                data_dir=os.path.join(td, name),
                host=host,
                cluster=cluster,
                **quiet,
            )
            s.open()
            for h in sorted([h0, h1]):
                if cluster.node_by_host(h) is None:
                    cluster.add_node(h)
            cluster.nodes.sort(key=lambda n: n.host)
            return s

        s0 = make("n0", h0)
        s1 = make("n1", h1)
        try:
            for s in (s0, s1):
                s.holder.create_index_if_not_exists("i")
                s.holder.index("i").create_frame_if_not_exists("f")
            # Seed bits straight into each OWNER's holder (no RPC):
            # the fault rules must fire on the read query's fan-out,
            # not get consumed by single-shot write legs.
            n_slices = 4
            for sl in range(n_slices):
                owner = s0.cluster.fragment_nodes("i", sl)[0].host
                srv = s0 if owner == h0 else s1
                srv.holder.frame("i", "f").set_bit(
                    "standard", 1, sl * SLICE_WIDTH
                )
            for s in (s0, s1):
                s.holder.index("i").set_remote_max_slice(n_slices - 1)
            c0 = InternalClient(s0.host, timeout=10.0)

            got = c0.execute_pql("i", 'Count(Bitmap(frame="f", rowID=1))')
            assert got == n_slices, f"chaos query answered {got}, want {n_slices}"

            plan = faults.active()
            assert plan is not None, "fault plan never loaded from env"
            fired = [r for r in plan.rules if r.hits > 0]
            assert fired, f"no fault rule fired: {plan.snapshot()}"
            print(
                "chaos-smoke ok: count exact under "
                f"{len(fired)}/{len(plan.rules)} fired fault rule(s); "
                f"rules={plan.snapshot()}"
            )
        finally:
            s0.close()
            s1.close()
    if os.environ.get("PILOSA_LOCK_CHECK"):
        # Runtime lock-order validation (PR 8): every acquisition order
        # observed during the chaos pass must be consistent with the
        # static lock graph (pilosa_tpu/analyze).
        from pilosa_tpu.analyze import runtime as lock_check

        problems = lock_check.verify()
        print(lock_check.report().splitlines()[0])
        if problems:
            for p in problems:
                print("lock-check DISAGREEMENT:", p)
            return 1
        print("lock-check ok: runtime order consistent with static graph")
    return 0


if __name__ == "__main__":
    sys.exit(main())
