"""CI smoke for the mesh-sharded data plane (``make multichip-smoke``).

Runs on the virtual 8-device CPU mesh (re-execs itself with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``, the same
harness as the tier-1 suite — a CPU wiring check; chip_smoke.py is the
check on real chips) and asserts,
in one process, the ISSUE-12 wiring contract:

* with >1 device visible, sharded execution engages BY DEFAULT — the
  executor's assembled batch is mesh-sharded and fragment planes are
  spread over the mesh shards (slice mod n_devices);
* a tiny mixed storm of DISTINCT Intersect+Count queries plus TopN,
  through the production path (coalescer + fusion + the ICI-reduced
  "total" launch), answers BYTE-IDENTICALLY to the forced
  single-device host path ([device] mesh-devices = 1) and to an
  independent numpy oracle;
* the interpreter program-cache entry counts stay within their derived
  hard bounds (``exec.programCache.entries[cache:interp] <= bound``).

Deterministic, seconds, no accelerator required — BLOCKING in
check.yml alongside resize-smoke/chaos-smoke.
"""

from __future__ import annotations

import os
import sys

if not os.environ.get("_MULTICHIP_SMOKE_REEXEC"):
    flags = os.environ.get("XLA_FLAGS", "")
    flags = " ".join(
        f for f in flags.split()
        if "xla_force_host_platform_device_count" not in f
    )
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count=8".strip()
    )
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["_MULTICHIP_SMOKE_REEXEC"] = "1"
    os.execv(sys.executable, [sys.executable] + sys.argv)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

N_SLICES = 11  # deliberately not a multiple of 8: exercises spill/pad
BITS_PER_ROW = 64
ROWS = 6


def log(msg: str) -> None:
    print(f"[multichip-smoke] {msg}", file=sys.stderr, flush=True)


def build(tmp: str):
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.ops.bitplane import SLICE_WIDTH

    rng = np.random.default_rng(23)
    holder = Holder(tmp)
    holder.open()
    idx = holder.create_index("i")
    f = idx.create_frame("f")
    bits: dict[int, set] = {r: set() for r in range(ROWS)}
    for r in range(ROWS):
        for s in range(N_SLICES):
            for c in rng.choice(SLICE_WIDTH // 64, size=BITS_PER_ROW, replace=False):
                col = s * SLICE_WIDTH + int(c)
                f.set_bit("standard", r, col)
                bits[r].add(col)
    return holder, bits


def run_queries(ex, parse_string, queries):
    from concurrent.futures import ThreadPoolExecutor

    def one(q):
        return ex.execute("i", parse_string(q))

    with ThreadPoolExecutor(max_workers=8) as pool:
        return list(pool.map(one, queries))


def main() -> int:
    import tempfile

    import jax

    from pilosa_tpu.exec import coalesce as coalesce_mod
    from pilosa_tpu.exec import plan
    from pilosa_tpu.exec.executor import Executor
    from pilosa_tpu.net import codec
    from pilosa_tpu.ops import bitplane as bp
    from pilosa_tpu.parallel import mesh as pmesh
    from pilosa_tpu.pql.parser import parse_string

    assert len(jax.local_devices()) == 8, jax.local_devices()
    assert bp.mesh_device_count() == 8
    assert pmesh.default_slices_mesh() is not None, (
        "sharded execution must engage by default with >1 device visible"
    )

    with tempfile.TemporaryDirectory() as tmp:
        holder, bits = build(tmp)

        # A distinct-query mix: pairwise Intersect+Count (fuses into
        # ICI-reduced "total" interpreter launches), row reads, TopN.
        count_qs = [
            f"Count(Intersect(Bitmap(rowID={a}, frame=f),"
            f" Bitmap(rowID={b}, frame=f)))"
            for a in range(ROWS)
            for b in range(a + 1, ROWS)
        ]
        row_q = "Bitmap(rowID=0, frame=f)"
        topn_q = "TopN(frame=f, n=4)"

        # --- sharded (default) pass, production path -------------------
        co = coalesce_mod.CoalesceScheduler()
        ex = Executor(holder, coalescer=co)
        try:
            sharded_counts = [
                int(r[0]) for r in run_queries(ex, parse_string, count_qs)
            ]
            (row_res,) = ex.execute("i", parse_string(row_q))
            sharded_bits = codec.bitmap_to_json(row_res)["bits"]
            (topn_res,) = ex.execute("i", parse_string(topn_q))
            sharded_topn = [(p.id, p.count) for p in topn_res]
            # The default batch really is mesh-sharded.
            call = parse_string(count_qs[0]).calls[0].children[0]
            ent = ex._cached_batch("i", call, list(range(N_SLICES)))
            assert ent["mesh"] is not None, "batch must be mesh-sharded"
            assert len(ent["batch"].devices()) == 8
            snap = co.snapshot()
            assert snap["launches"] > 0
        finally:
            ex.close()
            co.close()

        # Fragment planes spread over the mesh shards.
        view = holder.index("i").frame("f").view("standard")
        for frag in view.fragments():
            (dev,) = frag.device_plane().devices()
            assert dev == bp.home_device(frag.slice), (
                f"slice {frag.slice} plane on {dev}, want "
                f"{bp.home_device(frag.slice)}"
            )
        spread = {
            next(iter(f.device_plane().devices())) for f in view.fragments()
        }
        assert len(spread) == 8, f"planes on {len(spread)} devices, want 8"

        # --- numpy oracle ---------------------------------------------
        oracle = [
            len(bits[a] & bits[b])
            for a in range(ROWS)
            for b in range(a + 1, ROWS)
        ]
        assert sharded_counts == oracle, (sharded_counts, oracle)
        assert sharded_bits == sorted(bits[0])
        want_topn = sorted(
            ((r, len(bits[r])) for r in range(ROWS)),
            key=lambda p: (-p[1], p[0]),
        )[:4]
        assert sharded_topn == want_topn, (sharded_topn, want_topn)

        # --- forced single-device host path: byte-identical ------------
        bp.configure_mesh_devices(1)
        pmesh._slices_mesh = None
        try:
            assert pmesh.default_slices_mesh() is None
            co1 = coalesce_mod.CoalesceScheduler()
            ex1 = Executor(holder, coalescer=co1)
            try:
                host_counts = [
                    int(r[0]) for r in run_queries(ex1, parse_string, count_qs)
                ]
                (row1,) = ex1.execute("i", parse_string(row_q))
                host_bits = codec.bitmap_to_json(row1)["bits"]
                (topn1,) = ex1.execute("i", parse_string(topn_q))
                host_topn = [(p.id, p.count) for p in topn1]
            finally:
                ex1.close()
                co1.close()
        finally:
            bp.configure_mesh_devices(0)
            pmesh._slices_mesh = None
        assert sharded_counts == host_counts
        assert sharded_bits == host_bits
        assert sharded_topn == host_topn

        # --- interp program-cache entries within bounds ----------------
        stats = plan.program_cache_stats()
        bounds = plan.program_cache_bounds()
        assert stats["interp"] <= bounds["interp"], (stats, bounds)

        holder.close()

    log(
        f"OK: {len(count_qs)} distinct sharded counts + row + TopN "
        f"byte-identical to the single-device path and the numpy oracle;"
        f" planes spread over 8 shards; interp entries "
        f"{stats['interp']} <= bound {bounds['interp']}"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
