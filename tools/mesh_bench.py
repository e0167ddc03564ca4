"""``mesh_scaling`` bench tier: the mesh-sharded data plane measured
end to end (ISSUE 12 / ROADMAP open item 2).

Three sections, one JSON line on stdout:

* **curve** — devices-vs-Gcols/s at 1/2/4/8 devices: a real Holder +
  Executor (coalescer + fusion on, the production path) answering a
  concurrent Intersect+Count storm, with the ``[device] mesh-devices``
  cap selecting the mesh width.  Every point byte-checks against the
  host numpy reference AND against the single-device run — the sharded
  data plane must be invisible in results, visible only in placement.
* **headline** — the BASELINE configs[4] shape: an Intersect+Count at
  ``--headline-columns`` (default 10B columns ≈ 9537 slices) over the
  full mesh through the limb total-count program (the same ICI-reduced
  psum the executor's sharded path dispatches), byte-checked against
  the host count.
* **node_grid** — the real production topology: N HTTP nodes × M
  devices per node; every node of the grid runs the mesh-sharded plane
  over its owned slices and the coordinator reduces over HTTP while
  each node reduces its local slices over the (virtual) ICI.

On hosts without a multi-device accelerator the tier runs on the
virtual 8-device CPU mesh (XLA_FLAGS --xla_force_host_platform_device_
count=8, the same harness the tier-1 suite uses); scaling numbers
there measure WIRING, not speedup — all eight virtual devices share the
host cores.  Set MESH_BENCH_USE_BACKEND=1 to run the curve and headline
on the ambient JAX backend instead (a real multi-chip host).  The node
grid is CPU-only whatever the backend: its N node processes on one
host would each claim every chip, and partitioning chips among
processes is ROADMAP R5's work.
"""

from __future__ import annotations

import json
import os
import sys
import time

# Force the virtual 8-device CPU mesh BEFORE jax initializes, then
# re-exec so the flags latch (mirrors tests/conftest.py).
if os.environ.get("MESH_BENCH_USE_BACKEND") != "1" and not os.environ.get(
    "_MESH_BENCH_REEXEC"
):
    flags = os.environ.get("XLA_FLAGS", "")
    flags = " ".join(
        f for f in flags.split()
        if "xla_force_host_platform_device_count" not in f
    )
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count=8".strip()
    )
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["_MESH_BENCH_REEXEC"] = "1"
    os.execv(sys.executable, [sys.executable] + sys.argv)

import numpy as np  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def log(msg: str) -> None:
    print(f"[mesh] {msg}", file=sys.stderr, flush=True)


def _build_leaves(rng, n_slices: int, words: int) -> np.ndarray:
    return rng.integers(0, 2**32, size=(n_slices, 2, words), dtype=np.uint32)


def run_curve(leaves: np.ndarray, device_counts, queries: int, threads: int):
    """Executor end-to-end Gcols/s per mesh width."""
    import tempfile
    from concurrent.futures import ThreadPoolExecutor

    from pilosa_tpu.exec import coalesce as coalesce_mod
    from pilosa_tpu.exec.executor import Executor
    from pilosa_tpu.ops import bitplane as bp
    from pilosa_tpu.ops.bitplane import SLICE_WIDTH
    from pilosa_tpu.parallel import mesh as pmesh
    from pilosa_tpu.pql.parser import parse_string

    from bench import build_holder

    n_slices = leaves.shape[0]
    want = int(np.bitwise_count(leaves[:, 0] & leaves[:, 1]).sum())
    q = parse_string(
        "Count(Intersect(Bitmap(rowID=1, frame=f), Bitmap(rowID=2, frame=f)))"
    )
    curve: dict = {}
    for d in device_counts:
        bp.configure_mesh_devices(d)
        pmesh._slices_mesh = None  # rebuild the cached mesh at width d
        assert bp.mesh_device_count() == d, (bp.mesh_device_count(), d)
        with tempfile.TemporaryDirectory() as td:
            holder = build_holder(leaves, td)
            co = coalesce_mod.CoalesceScheduler()
            ex = Executor(holder, coalescer=co)
            try:
                got = int(ex.execute("i", q)[0])  # warm + byte-check
                assert got == want, f"devices={d}: {got} != {want}"
                t0 = time.perf_counter()
                with ThreadPoolExecutor(max_workers=threads) as pool:
                    res = list(
                        pool.map(
                            lambda _i: int(ex.execute("i", q)[0]),
                            range(queries),
                        )
                    )
                wall = time.perf_counter() - t0
                assert all(r == want for r in res)
                per_q = wall / queries
                sharded = pmesh.default_slices_mesh() is not None
                assert sharded == (d > 1)
                curve[str(d)] = {
                    "ms_per_query": round(per_q * 1e3, 3),
                    "gcols_per_s": round(
                        n_slices * SLICE_WIDTH / per_q / 1e9, 3
                    ),
                    "sharded": sharded,
                    "byte_identical": True,
                    "count": want,
                }
                log(
                    f"curve {d} device(s): {per_q*1e3:.2f} ms/query, "
                    f"{curve[str(d)]['gcols_per_s']} Gcols/s, "
                    f"sharded={sharded}"
                )
            finally:
                ex.close()
                co.close()
                holder.close()
    bp.configure_mesh_devices(0)
    pmesh._slices_mesh = None
    return curve


def run_headline(columns: int, rng) -> dict:
    """Intersect+Count at ``columns`` over the full mesh: the sharded
    limb total-count (psum over the slices axis), byte-checked."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pilosa_tpu.exec import plan
    from pilosa_tpu.ops.bitplane import SLICE_WIDTH, WORDS_PER_SLICE
    from pilosa_tpu.parallel import mesh as pmesh
    from pilosa_tpu.pql.parser import parse_string

    n_slices = (columns + SLICE_WIDTH - 1) // SLICE_WIDTH
    n_dev = len(jax.local_devices())
    pad = (-n_slices) % n_dev
    log(
        f"headline: {columns} columns = {n_slices} slices (+{pad} pad) "
        f"over {n_dev} devices"
    )
    mesh = pmesh.slice_mesh(n_dev)
    leaves = _build_leaves(rng, n_slices, WORDS_PER_SLICE)
    t0 = time.perf_counter()
    want = int(np.bitwise_count(leaves[:, 0] & leaves[:, 1]).sum())
    host_s = time.perf_counter() - t0
    log(f"host AND+popcount: {host_s:.2f}s -> {want}")
    if pad:
        leaves = np.concatenate(
            [leaves, np.zeros((pad,) + leaves.shape[1:], leaves.dtype)]
        )
    batch = jax.device_put(
        leaves, NamedSharding(mesh, P(pmesh.AXIS_SLICES, None, None))
    )
    jax.block_until_ready(batch)
    q = parse_string("Count(Intersect(Bitmap(rowID=1), Bitmap(rowID=2)))")
    expr, _ = plan.decompose(q.calls[0].children[0])
    fn = plan.compiled_total_count(expr, mesh)
    # Warm (compile) + byte-check, then best-of-N timed passes; the
    # limb fetch forces completion (8 bytes home per pass).
    got = plan.recombine_count_limbs(jax.device_get(fn(batch)))
    assert got == want, f"headline byte-check: {got} != {want}"
    best = float("inf")
    passes = int(os.environ.get("MESH_BENCH_HEADLINE_PASSES", "3"))
    for _ in range(passes):
        t0 = time.perf_counter()
        limbs = jax.device_get(fn(batch))
        best = min(best, time.perf_counter() - t0)
    assert plan.recombine_count_limbs(limbs) == want
    gcols = n_slices * SLICE_WIDTH / best / 1e9
    log(f"headline: {best*1e3:.2f} ms/pass, {gcols:.1f} Gcols/s")
    return {
        "columns": n_slices * SLICE_WIDTH,
        "slices": n_slices,
        "devices": n_dev,
        "ms_per_pass": round(best * 1e3, 3),
        "gcols_per_s": round(gcols, 3),
        "host_reference_s": round(host_s, 3),
        "count": want,
        "byte_identical": True,
    }


def _free_tcp_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _boot_grid_node(tmp: str, name: str, host: str, ring, m_devices: int):
    """One real node in its OWN process (its own JAX runtime and
    virtual mesh — the production topology, and the only sound one:
    in-process nodes would share one device set, which collectives
    cannot)."""
    import subprocess

    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PILOSA_DATA_DIR=f"{tmp}/{name}",
        PILOSA_HOST=host,
        PILOSA_CLUSTER_HOSTS=",".join(ring),
        PILOSA_CLUSTER_POLLING_INTERVAL="1",
        PILOSA_ANTI_ENTROPY_INTERVAL="3600",
        PILOSA_DEVICE_MESH_DEVICES=str(m_devices),
        PILOSA_TPU_PREWARM="false",
    )
    flags = env.get("XLA_FLAGS", "")
    flags = " ".join(
        f for f in flags.split()
        if "xla_force_host_platform_device_count" not in f
    )
    env["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count=8".strip()
    )
    env.pop("_MESH_BENCH_REEXEC", None)
    return subprocess.Popen(
        [sys.executable, "-m", "pilosa_tpu.cli", "server"],
        env=env,
        cwd=REPO,
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
    )


def _wait_ready(host: str, timeout: float = 120.0) -> None:
    from pilosa_tpu.net.client import InternalClient

    client = InternalClient(host, timeout=2.0)
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            status, data = client._request("GET", "/version")
            client._check(status, data)
            return
        except Exception:  # noqa: BLE001 — still booting
            time.sleep(0.2)
    raise SystemExit(f"FAIL: grid node {host} never became ready")


def run_node_grid(node_counts, device_counts, n_slices: int, bits: int) -> dict:
    """N HTTP nodes × M devices per node — the production topology.
    One PROCESS per node (own JAX runtime, own virtual 8-device mesh;
    [device] mesh-devices selects each node's width), a seeded sparse
    corpus imported over HTTP, and a concurrent Intersect+Count storm
    through the coordinator, byte-checked against the host reference."""
    import tempfile
    from concurrent.futures import ThreadPoolExecutor

    from pilosa_tpu.net.client import ClientError, InternalClient
    from pilosa_tpu.ops.bitplane import SLICE_WIDTH

    rng = np.random.default_rng(17)
    # Two overlapping sparse rows per slice; the Intersect count is
    # host-derivable exactly.
    cols1 = [
        rng.choice(SLICE_WIDTH, size=bits, replace=False)
        for _ in range(n_slices)
    ]
    cols2 = [
        rng.choice(SLICE_WIDTH, size=bits, replace=False)
        for _ in range(n_slices)
    ]
    want = sum(
        len(np.intersect1d(c1, c2)) for c1, c2 in zip(cols1, cols2)
    )
    q = 'Count(Intersect(Bitmap(rowID=1, frame="f"), Bitmap(rowID=2, frame="f")))'
    grid: dict = {}
    for m in device_counts:
        for n_nodes in node_counts:
            tmp = tempfile.mkdtemp(prefix=f"mesh-grid-{n_nodes}x{m}-")
            hosts = sorted(f"127.0.0.1:{_free_tcp_port()}" for _ in range(n_nodes))
            procs = []
            try:
                for i, h in enumerate(hosts):
                    procs.append(_boot_grid_node(tmp, f"n{i}", h, hosts, m))
                for h in hosts:
                    _wait_ready(h)
                c0 = InternalClient(hosts[0], timeout=60.0)
                for h in hosts:
                    ch = InternalClient(h, timeout=10.0)
                    for call in ("create_index", "create_frame"):
                        try:
                            getattr(ch, call)(*("i",) if call == "create_index" else ("i", "f"))
                        except ClientError:
                            pass
                for sl in range(n_slices):
                    for row, cols in ((1, cols1[sl]), (2, cols2[sl])):
                        c0.import_bits(
                            "i", "f", sl,
                            (np.full(len(cols), row, np.int64),
                             cols.astype(np.int64) + sl * SLICE_WIDTH),
                        )
                # 1 s polling propagates the slice range; wait for the
                # corpus to converge on the coordinator.
                deadline = time.time() + 60
                while time.time() < deadline:
                    try:
                        if int(c0.execute_query("i", q)[0]) == want:
                            break
                    except (ClientError, ConnectionError):
                        pass
                    time.sleep(0.3)
                got = int(c0.execute_query("i", q)[0])
                assert got == want, f"grid {n_nodes}x{m}: {got} != {want}"
                n_conc, threads = 24, 8
                clients = [
                    InternalClient(hosts[0], timeout=60.0)
                    for _ in range(threads)
                ]
                t0 = time.perf_counter()
                with ThreadPoolExecutor(max_workers=threads) as pool:
                    res = list(
                        pool.map(
                            lambda i: int(
                                clients[i % threads].execute_query("i", q)[0]
                            ),
                            range(n_conc),
                        )
                    )
                per_q = (time.perf_counter() - t0) / n_conc
                assert all(r == want for r in res)
                grid[f"{n_nodes}x{m}"] = {
                    "nodes": n_nodes,
                    "devices_per_node": m,
                    "concurrent_ms_per_query": round(per_q * 1e3, 3),
                    "gcols_per_s": round(
                        n_slices * SLICE_WIDTH / per_q / 1e9, 3
                    ),
                    "byte_identical": True,
                }
                log(
                    f"grid {n_nodes} node(s) x {m} device(s): "
                    f"{per_q*1e3:.2f} ms/query"
                )
            finally:
                for p in procs:
                    p.terminate()
                for p in procs:
                    try:
                        p.wait(timeout=20)
                    except Exception:  # noqa: BLE001
                        p.kill()
    return grid


def main() -> int:
    import argparse

    import jax

    p = argparse.ArgumentParser()
    p.add_argument(
        "--slices", type=int,
        default=int(os.environ.get("BENCH_MESH_SLICES", "64")),
    )
    p.add_argument(
        "--headline-columns", type=int,
        default=int(os.environ.get("BENCH_MESH_COLUMNS", str(10**10))),
    )
    p.add_argument("--queries", type=int, default=48)
    p.add_argument("--threads", type=int, default=8)
    args = p.parse_args()

    n_local = len(jax.local_devices())
    device_counts = [d for d in (1, 2, 4, 8) if d <= n_local]
    rng = np.random.default_rng(13)
    log(
        f"backend={jax.default_backend()} devices={n_local} "
        f"curve slices={args.slices} headline columns={args.headline_columns}"
    )

    from pilosa_tpu.ops.bitplane import WORDS_PER_SLICE

    leaves = _build_leaves(rng, args.slices, WORDS_PER_SLICE)
    curve = run_curve(leaves, device_counts, args.queries, args.threads)
    node_grid = run_node_grid(
        node_counts=(1, 2),
        device_counts=list(dict.fromkeys([1, device_counts[-1]])),
        n_slices=min(args.slices, 8),
        bits=int(os.environ.get("BENCH_MESH_GRID_BITS", "512")),
    )
    headline = run_headline(args.headline_columns, rng)

    out = {
        "backend": jax.default_backend(),
        "n_devices_visible": n_local,
        "virtual_mesh": os.environ.get("_MESH_BENCH_REEXEC") == "1",
        "curve": curve,
        "node_grid": node_grid,
        "headline": headline,
    }
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
