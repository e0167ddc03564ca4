"""Compressed-plane smoke (``make sparse-smoke``): a tiny 1%-density
clustered corpus on the CPU backend, asserting the PR-19 container
format pipeline end to end in seconds:

* write-time format selection picks compressed containers (RLE for the
  clustered rows, packed positions for the scattered row — no corpus
  row may stay dense at 1%);
* every executor answer over the compressed planes is byte-checked
  against an independent numpy set oracle, and Count results route
  through the anchored position-domain kernels (the plan.anchored
  program family is non-empty afterwards);
* paging rows through ``device_row`` leaves the fragment's sparse pool
  resident at >= 10x below its logical dense geometry, with the
  format mix annotated in the /debug/hbm snapshot;
* the anchored launch site's effective bytes sit below its logical
  bytes in /debug/perf.

Runs under ``PILOSA_LOCK_CHECK=1`` in CI like subscribe-smoke: the
runtime lock-acquisition order the compressed read path produces must
stay consistent with the static lock graph.
"""

from __future__ import annotations

import os
import shutil
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def log(msg: str) -> None:
    print(f"[sparse-smoke] {msg}", file=sys.stderr)


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

    # Zero dense budget: every row lands in the sparse tier where the
    # compressed device path engages.
    orig_init = fr.Fragment.__init__

    def sparse_init(self, *a, **kw):
        kw.setdefault("dense_row_budget", 0)
        orig_init(self, *a, **kw)

    fr.Fragment.__init__ = sparse_init
    tmp = tempfile.mkdtemp(prefix="sparse_smoke_")
    try:
        h = Holder(os.path.join(tmp, "data"))
        h.open()
        c = new_cluster(1)
        ex = Executor(h, host=c.nodes[0].host, cluster=c)
        idx = h.create_index_if_not_exists("i")
        f = idx.create_frame_if_not_exists("f")

        rng = np.random.default_rng(99)
        sw = bp.SLICE_WIDTH
        card = int(0.01 * sw)  # 1% density
        oracle: dict[int, set] = {}
        rows_in, cols_in = [], []
        for row in range(9):
            cols: set = set()
            if row == 1:
                for p in rng.choice(sw, size=card, replace=False):
                    cols.add(int(p))
            else:
                run_len = card // 8
                for st in rng.choice(sw - run_len, size=8, replace=False):
                    cols.update(range(int(st), int(st) + run_len))
            oracle[row] = cols
            for cc in sorted(cols):
                rows_in.append(row)
                cols_in.append(cc)
        f.import_bulk(rows_in, cols_in)

        # --- format mix: no dense rows at 1% -------------------------
        frag = h.fragment("i", "f", VIEW_STANDARD, 0)
        mix: dict[str, int] = {}
        for row in range(9):
            fmt, _p, nbytes, fcard = frag.host_payload(row)
            mix[bp.FMT_NAMES[fmt]] = mix.get(bp.FMT_NAMES[fmt], 0) + 1
            assert fcard == len(oracle[row]), (row, fcard, len(oracle[row]))
        log(f"format mix: {mix}")
        assert mix.get("rle", 0) == 8, mix
        assert mix.get("sparse", 0) == 1, mix
        assert mix.get("dense", 0) == 0, mix

        # --- byte-check vs the numpy oracle --------------------------
        def q(pql):
            return ex.execute("i", parse_string(pql), None, None)

        plan.clear_program_caches()
        checks = 0
        for a in range(9):
            b = (a + 1) % 9
            (cnt,) = q(
                f"Count(Intersect(Bitmap(rowID={a}, frame=f),"
                f" Bitmap(rowID={b}, frame=f)))"
            )
            assert cnt == len(oracle[a] & oracle[b]), (a, b, cnt)
            (cnt,) = q(
                f"Count(Union(Bitmap(rowID={a}, frame=f),"
                f" Bitmap(rowID={b}, frame=f)))"
            )
            assert cnt == len(oracle[a] | oracle[b]), (a, b, cnt)
            (cnt,) = q(
                f"Count(Difference(Bitmap(rowID={a}, frame=f),"
                f" Bitmap(rowID={b}, frame=f)))"
            )
            assert cnt == len(oracle[a] - oracle[b]), (a, b, cnt)
            (bm,) = q(f"Bitmap(rowID={a}, frame=f)")
            assert bm.bits() == sorted(oracle[a]), a
            checks += 4
        anchored_programs = plan.program_cache_stats().get("plan.anchored", 0)
        log(f"{checks} answers byte-checked; "
            f"{anchored_programs} anchored programs compiled")
        assert anchored_programs > 0, "anchored route never engaged"

        # --- compressed residency ------------------------------------
        for row in range(9):
            assert frag.device_row(row) is not None
        snap = device_mod.pool().snapshot()
        sparse_rows = [
            fent
            for fent in snap["fragments"]
            if fent.get("kind") == "sparse"
            and str(fent.get("fragment", "")).startswith("i")
        ]
        assert sparse_rows, snap["fragments"]
        res = sum(fent["bytes"] for fent in sparse_rows)
        logi = sum(fent["logical_bytes"] for fent in sparse_rows)
        ratio = logi / res if res else 0.0
        fmts_note = sparse_rows[0].get("formats")
        log(
            f"resident {res} B vs logical {logi} B ({ratio:.1f}x), "
            f"pool formats {fmts_note}"
        )
        assert ratio >= 10, (res, logi)
        assert isinstance(fmts_note, dict) and fmts_note, sparse_rows[0]

        # --- effective vs logical launch bytes -----------------------
        site = perf_mod.registry().snapshot()["sites"].get("anchored")
        assert site is not None and site["launches"] >= 1, site
        assert 0 < site["eff_bytes"] < site["bytes"], site
        log(
            f"anchored site: {site['launches']} launches, "
            f"{site['eff_bytes']} effective of {site['bytes']} logical B"
        )

        h.close()
        log("sparse smoke OK")
    finally:
        fr.Fragment.__init__ = orig_init
        shutil.rmtree(tmp, ignore_errors=True)

    if os.environ.get("PILOSA_LOCK_CHECK"):
        # Runtime lock-order validation: the compressed read path's
        # acquisition order (fragment lock -> pool lock) must stay
        # consistent with the static lock graph (pilosa_tpu/analyze).
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
    raise SystemExit(main())
