"""Compile seconds against launches per answer for the fused TopN
scorer, by the number of fragments one program takes (ROADMAP M5).

    python tools/topn_scorer_sweep.py [--slices 954] [--rows 64] [--groups 8,16,32,64,128]

The scorer (``ops/bitplane.self_src_scorer``: on the TPU the kernel of
``_score_planes_kernel`` where the planes are no taller than the slots)
is one jitted program over a *tuple* of plane mirrors, unrolled once per
member, so its compile time grows with the tuple.  This holds ``--slices`` planes
of ``--rows`` rows on the device, as an index of that size does, and for
each group size G times (a) the first call, with the persistent compile
cache off, and (b) a whole answer: ceil(slices / G) launches of that one
program dispatched without waiting, then one fetch of every result.
One JSON line on stdout, also written to
``chiprun_out/topn_scorer_sweep.json``.  The numbers are the device's
only when the line's ``device.platform`` says so.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--slices", type=int, default=954)
    ap.add_argument("--rows", type=int, default=64)
    ap.add_argument("--groups", default="8,16,32,64,128")
    ap.add_argument("--repeats", type=int, default=7)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    jax.config.update("jax_enable_compilation_cache", False)
    from pilosa_tpu.ops import bitplane as bp

    dev = jax.devices()[0]
    kernel = bp.kernel_scores((args.rows, bp.WORDS_PER_SLICE), args.rows)
    score = bp.self_src_scorer(dev.platform, (args.rows, bp.WORDS_PER_SLICE), args.rows)
    make = jax.jit(
        lambda key: jax.random.bits(key, (args.rows, bp.WORDS_PER_SLICE), "uint32")
    )
    keys = jax.random.split(jax.random.PRNGKey(0), args.slices)
    planes = [make(k) for k in keys]
    jax.block_until_ready(planes)
    plane_bytes = args.rows * bp.WORDS_PER_SLICE * 4

    out = {
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": jax.device_count()},
        "slices": args.slices, "rows": args.rows,
        "kernel": kernel and dev.platform == "tpu",
        "bytes_per_answer": (args.rows + 1) * args.slices * bp.WORDS_PER_SLICE * 4,
        "groups": [],
    }
    slots_row = np.arange(args.rows, dtype=np.int32)
    for g in (int(x) for x in args.groups.split(",")):
        chunks = [planes[i:i + g] for i in range(0, args.slices, g)]
        chunks[-1] = chunks[-1] + [chunks[-1][-1]] * (g - len(chunks[-1]))
        chunks = [tuple(c) for c in chunks]
        slots = np.tile(slots_row, (g, 1))
        src_slots = np.full(g, 3, dtype=np.int32)

        t0 = time.monotonic()
        score(chunks[0], slots, src_slots).block_until_ready()
        first_call_s = time.monotonic() - t0

        dispatch_ms, answer_ms = [], []
        for _ in range(args.repeats):
            t0 = time.monotonic()
            outs = [score(c, slots, src_slots) for c in chunks]
            t1 = time.monotonic()
            jax.device_get(outs)
            t2 = time.monotonic()
            dispatch_ms.append((t1 - t0) * 1e3)
            answer_ms.append((t2 - t0) * 1e3)
        out["groups"].append({
            "G": g, "launches": len(chunks), "first_call_s": first_call_s,
            "dispatch_ms": statistics.median(dispatch_ms),
            "answer_ms": statistics.median(answer_ms),
            "answer_ms_min": min(answer_ms),
            "plane_mb_per_launch": g * plane_bytes / 1e6,
        })
        print(json.dumps(out["groups"][-1]), file=sys.stderr, flush=True)
    line = json.dumps(out)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "topn_scorer_sweep.json"), "w") as f:
        f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
