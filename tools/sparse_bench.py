"""Sparse bench tier (bench.py ``sparse``): what compressed device
planes buy when rows are far below dense occupancy.

Four corpora at 50% / 5% / 1% / 0.1% row density (even rows clustered
runs, odd rows uniform scatter — exercising the RLE, sparse-position,
and dense container formats the write-time selector picks between),
each driven through a distinct-query Count storm in two arms:

* **auto** — write-time per-row format selection on (the default);
  eligible fold-only counts route through the anchored position-domain
  kernels and read bytes proportional to density.
* **dense** — ``configure_plane_format("dense")``: every row a full
  128 KiB word plane, the pre-PR-19 path.

Reports per density: effective Gcols/s per arm, the speedup, the bytes
the device actually read (the perf registry's effective-byte counter
for the anchored site) vs the logical dense geometry, the container
format mix, and — at 1% and 0.1% — the compressed-vs-logical resident
HBM ratio after paging every row through ``device_row``.  A PQL storm
(Count over Intersect/Union/Difference, Bitmap, TopN, Range, Sum) runs
in both arms and the artifact's ``byte_identical`` flag asserts the
results match bit for bit; the tool exits non-zero on any divergence.

Timing figures are only meaningful on a real accelerator — bench-smoke
asserts the correctness/wiring fields (byte identity, format mix,
resident ratio), never the speedup.

Scale knobs: ``BENCH_SPARSE_SLICES`` (default 2), ``BENCH_SPARSE_ROWS``
(default 6), ``BENCH_SPARSE_REPS`` (timing reps per density, default 6).
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

DENSITIES = [(0.5, "50"), (0.05, "5"), (0.01, "1"), (0.001, "0.1")]


def log(msg: str) -> None:
    print(f"[sparse] {msg}", file=sys.stderr)


def build_corpus(holder, index, density, slices, n_rows, rng):
    """Row 1 uniform scatter (-> packed positions, or dense when the
    density is high enough that positions cost more than words), every
    other row clustered runs (-> RLE) — the mostly-clustered shape real
    bitmap data takes (the reason roaring carries a run container)."""
    import numpy as np

    from pilosa_tpu.ops import bitplane as bp

    idx = holder.create_index_if_not_exists(index)
    f = idx.create_frame_if_not_exists("f")
    f.set_options(range_enabled=True)
    if f.bsi_field("v") is None:
        f.create_field("v", 0, 1000)
    sw = bp.SLICE_WIDTH
    card = max(8, int(density * sw))
    oracle: dict[int, set] = {}
    rows_out, cols_out = [], []
    for row in range(n_rows):
        cols = set()
        for s in range(slices):
            base_off = s * sw
            if row == 1:
                pos = rng.choice(sw, size=card, replace=False)
                cols.update(int(p) + base_off for p in pos)
            else:
                # clustered: ~16 runs covering `card` positions
                n_runs = min(16, card)
                run_len = max(1, card // n_runs)
                starts = rng.choice(
                    max(1, sw - run_len), size=n_runs, replace=False
                )
                for st in starts:
                    cols.update(
                        range(base_off + int(st), base_off + int(st) + run_len)
                    )
        oracle[row] = cols
        for c in sorted(cols):
            rows_out.append(row)
            cols_out.append(c)
    f.import_bulk(rows_out, cols_out)
    # BSI values on a tail of row 0's columns so Range/Sum touch the
    # compressed-format fragment family too.
    vcols = sorted(oracle[0])[: min(500, len(oracle[0]))]
    f.import_value("v", vcols, [(c % 1000) for c in vcols])
    return f, oracle


def storm(ex, index, parse, n_rows):
    """The byte-identity PQL storm: one result list, order-stable."""
    pairs = [(i, (i + 1) % n_rows) for i in range(n_rows)]
    out = []
    for a, b in pairs:
        for shape in (
            f"Count(Intersect(Bitmap(rowID={a}, frame=f),"
            f" Bitmap(rowID={b}, frame=f)))",
            f"Count(Union(Bitmap(rowID={a}, frame=f),"
            f" Bitmap(rowID={b}, frame=f)))",
            f"Count(Difference(Bitmap(rowID={a}, frame=f),"
            f" Bitmap(rowID={b}, frame=f)))",
        ):
            (r,) = ex.execute(index, parse(shape), None, None)
            out.append(("count", shape, int(r)))
    (bm,) = ex.execute(index, parse("Bitmap(rowID=0, frame=f)"), None, None)
    out.append(("bitmap", "row0", tuple(bm.bits())))
    (tn,) = ex.execute(index, parse("TopN(frame=f, n=3)"), None, None)
    out.append(("topn", "n3", tuple((p.id, p.count) for p in tn)))
    (rg,) = ex.execute(
        index, parse("Range(frame=f, v > 500)"), None, None
    )
    out.append(("range", "v>500", tuple(rg.bits())))
    (sm,) = ex.execute(index, parse("Sum(frame=f, field=v)"), None, None)
    out.append(("sum", "v", (int(sm.value), int(sm.count))))
    return out


def count_loop(ex, index, parse, n_rows, reps):
    """Distinct Count(Intersect) queries (defeating the assembled-batch
    cache) — the timing workload."""
    t0 = time.perf_counter()
    total = 0
    for r in range(reps):
        a = r % n_rows
        b = (r + 1 + (r % max(1, n_rows - 1))) % n_rows
        if a == b:
            b = (b + 1) % n_rows
        (c,) = ex.execute(
            index,
            parse(
                f"Count(Intersect(Bitmap(rowID={a}, frame=f),"
                f" Bitmap(rowID={b}, frame=f)))"
            ),
            None,
            None,
        )
        total += int(c)
    return time.perf_counter() - t0, total


def main() -> int:
    import numpy as np

    import pilosa_tpu.core.fragment as fr
    from pilosa_tpu import device as device_mod
    from pilosa_tpu.cluster.topology import new_cluster
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.core.view import VIEW_STANDARD
    from pilosa_tpu.exec import plan
    from pilosa_tpu.exec.executor import Executor
    from pilosa_tpu.obs import perf as perf_mod
    from pilosa_tpu.ops import bitplane as bp
    from pilosa_tpu.pql.parser import parse_string

    slices = int(os.environ.get("BENCH_SPARSE_SLICES", "2"))
    n_rows = int(os.environ.get("BENCH_SPARSE_ROWS", "6"))
    reps = int(os.environ.get("BENCH_SPARSE_REPS", "6"))
    sw = bp.SLICE_WIDTH

    # Zero dense budget: every row lands in the sparse tier, where the
    # device path pages compressed payloads instead of full planes.
    orig_init = fr.Fragment.__init__

    def sparse_init(self, *a, **kw):
        kw.setdefault("dense_row_budget", 0)
        orig_init(self, *a, **kw)

    fr.Fragment.__init__ = sparse_init
    tmp = tempfile.mkdtemp(prefix="sparse_bench_")
    densities_out: dict[str, dict] = {}
    ok = True
    try:
        h = Holder(os.path.join(tmp, "data"))
        h.open()
        c = new_cluster(1)
        ex = Executor(h, host=c.nodes[0].host, cluster=c)
        rng = np.random.default_rng(1234)
        for density, tag in DENSITIES:
            index = f"sb{tag.replace('.', '_')}"
            frame, oracle = build_corpus(
                h, index, density, slices, n_rows, rng
            )

            # format mix across every (row, slice)
            mix: dict[str, int] = {}
            logical_rows = 0
            compressed_bytes = 0
            for s in range(slices):
                frag = h.fragment(index, "f", VIEW_STANDARD, s)
                if frag is None:
                    continue
                for row in range(n_rows):
                    hp = frag.host_payload(row)
                    if hp is None:
                        continue
                    fmt, _payload, nbytes, _card = hp
                    mix[bp.FMT_NAMES[fmt]] = mix.get(bp.FMT_NAMES[fmt], 0) + 1
                    logical_rows += 1
                    compressed_bytes += nbytes

            # auto arm: storm for identity, loop for timing, perf deltas
            bp.configure_plane_format(mode="auto")
            plan.clear_program_caches()
            auto_storm = storm(ex, index, parse_string, n_rows)
            count_loop(ex, index, parse_string, n_rows, reps)  # warm compiles
            site0 = (
                perf_mod.registry()
                .snapshot()["sites"]
                .get("anchored", {"bytes": 0, "eff_bytes": 0})
            )
            t_auto, total_a = count_loop(ex, index, parse_string, n_rows, reps)
            site1 = (
                perf_mod.registry()
                .snapshot()["sites"]
                .get("anchored", {"bytes": 0, "eff_bytes": 0})
            )
            eff_read = site1.get("eff_bytes", 0) - site0.get("eff_bytes", 0)
            logical_read = site1.get("bytes", 0) - site0.get("bytes", 0)

            # dense arm: same storms with per-row formats forced off
            bp.configure_plane_format(mode="dense")
            plan.clear_program_caches()
            dense_storm = storm(ex, index, parse_string, n_rows)
            count_loop(ex, index, parse_string, n_rows, reps)  # warm compiles
            t_dense, total_d = count_loop(
                ex, index, parse_string, n_rows, reps
            )
            bp.configure_plane_format(mode="auto")

            identical = auto_storm == dense_storm and total_a == total_d
            if not identical:
                ok = False
                for qa, qd in zip(auto_storm, dense_storm):
                    if qa != qd:
                        log(f"DIVERGENCE at {density}: {qa} != {qd}")

            cols_scanned = reps * slices * sw
            entry = {
                "density_pct": density * 100,
                "effective_gcols_s": round(cols_scanned / t_auto / 1e9, 4),
                "dense_gcols_s": round(cols_scanned / t_dense / 1e9, 4),
                "speedup": round(t_dense / t_auto, 2) if t_auto > 0 else 0.0,
                "bytes_read": int(eff_read),
                "logical_bytes": int(logical_read),
                "format_mix": mix,
                "compressed_row_bytes": compressed_bytes,
                "logical_row_bytes": logical_rows * bp.WORDS_PER_SLICE * 4,
                "byte_identical": identical,
                "storm_queries": len(auto_storm),
            }

            # resident HBM ratio: page every row through device_row and
            # read this corpus's sparse-pool entries back out of the
            # /debug/hbm snapshot.
            if density <= 0.01:
                for s in range(slices):
                    frag = h.fragment(index, "f", VIEW_STANDARD, s)
                    if frag is None:
                        continue
                    for row in range(n_rows):
                        frag.device_row(row)
                snap = device_mod.pool().snapshot()
                res = sum(
                    fent["bytes"]
                    for fent in snap["fragments"]
                    if fent.get("kind") == "sparse"
                    and str(fent.get("fragment", "")).startswith(index)
                )
                logi = sum(
                    fent["logical_bytes"]
                    for fent in snap["fragments"]
                    if fent.get("kind") == "sparse"
                    and str(fent.get("fragment", "")).startswith(index)
                )
                entry["resident_bytes"] = res
                entry["resident_logical_bytes"] = logi
                entry["resident_ratio"] = (
                    round(logi / res, 1) if res else 0.0
                )
            densities_out[tag] = entry
            log(
                f"density {tag}%: auto {entry['effective_gcols_s']} vs dense"
                f" {entry['dense_gcols_s']} Gcols/s ({entry['speedup']}x),"
                f" read {eff_read} of {logical_read} logical bytes,"
                f" mix {mix}, identical={identical}"
            )
        h.close()
    finally:
        fr.Fragment.__init__ = orig_init
        shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps({"densities": densities_out}))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
