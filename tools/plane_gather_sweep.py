"""Device and dispatch time of the leaf-batch gather of a batch-cache
miss, by the form of its program and the number of fragments it takes
(ROADMAP S1 / S4).

    python tools/plane_gather_sweep.py [--slices 954] [--rows 64] [--leaves 2] [--groups 32,64,128]

Holds ``--slices`` plane mirrors of ``--rows`` rows on the device, as an
index of that size does, and times a whole batch (ceil(slices / G)
launches dispatched without waiting, each written in place into the
zeroed block of the slice bucket, then one wait) for each form:

* ``table``   ``bp.gather_planes`` + ``bp.place_rows``, what the executor
              runs: one ``dynamic_slice`` a row, masked once; the slots a
              resident table and the offsets resident scalars, so a
              launch's operands cross no host boundary;
* ``dslice``  the same program, its slots and offsets host numbers (four
              small transfers a launch);
* ``index``   ``plane[slots]`` a member, masked, stacked; host numbers;
* ``fused``   ``index`` and the in-place write as ONE program with the
              block donated (its key then holds the block's shape).

One JSON line on stdout, also written to
``chiprun_out/plane_gather_sweep.json``.  The numbers are the device's
only when the line's ``device.platform`` says so.
"""

from __future__ import annotations

import argparse
import functools
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
    ap.add_argument("--leaves", type=int, default=2)
    ap.add_argument("--groups", default="32,64,128")
    ap.add_argument("--repeats", type=int, default=7)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    jax.config.update("jax_enable_compilation_cache", False)
    from pilosa_tpu.exec import plan
    from pilosa_tpu.ops import bitplane as bp

    dev = jax.devices()[0]
    words = bp.WORDS_PER_SLICE
    make = jax.jit(lambda key: jax.random.bits(key, (args.rows, words), "uint32"))
    planes = [make(k) for k in jax.random.split(jax.random.PRNGKey(0), args.slices)]
    jax.block_until_ready(planes)
    rng = np.random.default_rng(0)
    slots = rng.integers(0, args.rows, (args.slices, args.leaves)).astype(np.int32)
    slots[::17, 0] = -1  # a row some fragments do not hold
    block_shape = (plan.slice_bucket(args.slices), args.leaves, words)

    @jax.jit
    def dslice(group, sl, valid):
        rows = [
            jax.lax.dynamic_slice_in_dim(group[f], sl[f, j], 1, axis=0)
            for f in range(len(group))
            for j in range(sl.shape[1])
        ]
        out = jnp.concatenate(rows).reshape(len(group), sl.shape[1], -1)
        return jnp.where(valid[:, :, None], out, jnp.uint32(0))

    @jax.jit
    def index(group, sl, valid):
        return jnp.stack([
            jnp.where(valid[f][:, None], group[f][sl[f]], jnp.uint32(0))
            for f in range(len(group))
        ])

    @functools.partial(jax.jit, donate_argnums=0)
    def place(block, rows, row0, col0):
        return jax.lax.dynamic_update_slice(block, rows, (row0, col0, 0))

    @functools.partial(jax.jit, donate_argnums=0)
    def fused(block, group, sl, valid, row0):
        out = jnp.stack([
            jnp.where(valid[f][:, None], group[f][sl[f]], jnp.uint32(0))
            for f in range(len(group))
        ])
        return jax.lax.dynamic_update_slice(block, out, (row0, 0, 0))

    def launches(g):
        for lo in range(0, args.slices, g):
            idx = np.minimum(np.arange(lo, lo + g), args.slices - 1)
            sl = slots[idx]
            valid = (sl >= 0) & (np.arange(lo, lo + g) < args.slices)[:, None]
            yield lo, tuple(planes[i] for i in idx), np.maximum(sl, 0), valid

    def batch(form, g):
        block = jnp.zeros(block_shape, dtype=jnp.uint32, device=dev)
        if form == "table":
            for t, out in enumerate(bp.gather_planes(planes, slots)):
                block = bp.place_rows(block, out, t * int(out.shape[0]))
            return block
        for lo, group, sl, valid in launches(g):
            if form == "fused":
                block = fused(block, group, sl, valid, np.int32(lo))
                continue
            fn = index if form == "index" else dslice
            block = place(block, fn(group, sl, valid), np.int32(lo), np.int32(0))
        return block

    want = None
    out = {
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": jax.device_count()},
        "slices": args.slices, "rows": args.rows, "leaves": args.leaves,
        "bytes_per_batch": args.slices * args.leaves * words * 4, "forms": [],
    }
    for g in (int(x) for x in args.groups.split(",")):
        if block_shape[0] % g:
            continue  # a padded last launch would write past the block
        for form in ("table", "dslice", "index", "fused"):
            if form == "table" and g != bp.SCORE_GROUP:
                continue  # the executor's group is bp.SCORE_GROUP
            t0 = time.monotonic()
            try:
                got = np.asarray(batch(form, g))
            except Exception as e:  # noqa: BLE001 — a form the compiler refuses
                out["forms"].append({"form": form, "G": g, "error": repr(e)[:300]})
                continue
            first_call_s = time.monotonic() - t0
            if want is None:
                want = got
            dispatch_ms, batch_ms = [], []
            for _ in range(args.repeats):
                t0 = time.monotonic()
                block = batch(form, g)
                t1 = time.monotonic()
                block.block_until_ready()
                t2 = time.monotonic()
                dispatch_ms.append((t1 - t0) * 1e3)
                batch_ms.append((t2 - t0) * 1e3)
            out["forms"].append({
                "form": form, "G": g, "launches": -(-args.slices // g),
                "equal": bool((got == want).all()), "first_call_s": first_call_s,
                "dispatch_ms": statistics.median(dispatch_ms),
                "batch_ms": statistics.median(batch_ms),
                "batch_ms_min": min(batch_ms),
            })
            print(json.dumps(out["forms"][-1]), file=sys.stderr, flush=True)
    line = json.dumps(out)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "plane_gather_sweep.json"), "w") as f:
        f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
