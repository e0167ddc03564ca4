"""Cold-start elimination: persistent XLA compile cache + shape pre-warm.

The reference's cold path is an O(containers) mmap open
(reference: fragment.go:154-242) — a restarted node answers its first
query in milliseconds.  Our executor instead compiles one fused XLA
program per (tree shape, slice bucket) on every process start.  Two
fixes, both here:

* ``enable_compile_cache()`` turns on JAX's persistent compilation
  cache so every shape is compiled once per MACHINE, not once per
  process — a restart deserializes the executable from disk.
* ``prewarm()`` compiles the standard query-shape buckets (the shapes
  every fresh server will hit: Count/row over 1–2-leaf trees at small
  power-of-two slice buckets), so even the first-ever query on a new
  machine finds its program ready.  Run it in a background thread at
  server open; it only touches jit caches, which are thread-safe.
"""

from __future__ import annotations

import os
import threading

import numpy as np

from pilosa_tpu.core.view import VIEW_INVERSE, VIEW_STANDARD
from pilosa_tpu.exec import plan
from pilosa_tpu.ops import bitplane as bp

# JAX's own variable for the cache directory.  Where it is set, JAX
# has already taken the directory from it and this module sets none.
ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"

# The directory is part of the cache key, so the default is ONE fixed
# path inside the checkout (git-ignored): a path under a data dir, a
# temp name, a pid or a time moves between runs and never hits.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax-compile-cache",
)

_enabled_dir: str | None = None
_lock = threading.Lock()


def resolve_cache_dir(configured: str = "") -> str | None:
    """The directory the persistent cache lives in:
    ``JAX_COMPILATION_CACHE_DIR`` when set, else an explicit
    ``[tpu] compilation-cache-dir``, else :data:`DEFAULT_CACHE_DIR`;
    None when the config says "off" and the variable is unset."""
    env_dir = os.environ.get(ENV_CACHE_DIR)
    if env_dir:
        return env_dir
    if configured == "off":
        return None
    return os.path.expanduser(configured) if configured else DEFAULT_CACHE_DIR


def enable_compile_cache(configured: str = "") -> str | None:
    """Turn JAX's persistent compilation cache on and return the
    directory in use (None when disabled by config or the directory
    cannot be created).

    With ``JAX_COMPILATION_CACHE_DIR`` set, only the entry threshold is
    touched — the directory stays the one JAX read from the variable.
    Idempotent; first caller wins (the cache dir is process-global in
    JAX).  The compile-time threshold is dropped to zero: with any
    other value a program that compiles in about that time lands on
    disk in one boot and not in the next, so "a restart compiles
    nothing" could not be checked.
    """
    global _enabled_dir
    with _lock:
        if _enabled_dir is not None:
            return _enabled_dir
        cache_dir = resolve_cache_dir(configured)
        if cache_dir is None:
            return None
        import jax

        if not os.environ.get(ENV_CACHE_DIR):
            try:
                os.makedirs(cache_dir, exist_ok=True)
            except OSError:
                return None
            jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        _enabled_dir = cache_dir
        return cache_dir


def enabled_cache_dir() -> str | None:
    return _enabled_dir


# The tree shapes every fresh node serves immediately: bare row fetch,
# Count(Bitmap), and the 2-leaf Intersect/Union/Difference counts —
# the reference's headline query mix (executor.go:418-505).
_LEAF = ("leaf", 0)
_STANDARD_EXPRS = (
    _LEAF,
    ("Intersect", ("leaf", 0), ("leaf", 1)),
    ("Union", ("leaf", 0), ("leaf", 1)),
    ("Difference", ("leaf", 0), ("leaf", 1)),
)


def _n_leaves(expr) -> int:
    if expr[0] == "leaf":
        return 1
    return sum(_n_leaves(e) for e in expr[1:])


# Bucket sizes the coalescer's concatenated launches land on: entry
# batches are pow2-padded per query, and distinct-entry concatenation
# re-pads the total to the next power of two (exec/coalesce.py).  The
# coalescer always runs the per-slice vmapped "count" program (NOT the
# limb total-count), so those jit keys need their own warm.
_COALESCE_BUCKETS = (1, 2, 4, 8, 16)


def prewarm_coalesce(
    buckets=_COALESCE_BUCKETS, exprs=_STANDARD_EXPRS[1:3]
) -> int:
    """Compile the coalescer's (tree shape x bucket) "count" programs —
    by default the Intersect/Union 2-leaf Count shapes, the headline
    concurrent query mix.  The "row" programs at small buckets are
    already covered by :func:`prewarm`; larger coalesced row buckets
    compile on first use (a row result that size is dominated by its
    own fetch, not the compile)."""
    warmed = 0
    for expr in exprs:
        nl = _n_leaves(expr)
        for bucket in buckets:
            batch = np.zeros((bucket, nl, bp.WORDS_PER_SLICE), dtype=np.uint32)
            plan.compiled_batched(expr, "count")(batch).block_until_ready()
            warmed += 1
    return warmed


# Interpreter geometry buckets the first fused launches land on:
# (leaf bucket, op-table bucket, out bucket) for the common small mixed
# batches — 2-leaf trees fusing in pairs/quads.  Larger geometries
# (BSI ripples push the op table toward 64-128 rows) compile on first
# use; a batch that size is dominated by its own pass, not the compile.
_FUSE_SHAPES = ((2, 8, 2), (4, 8, 2), (4, 8, 4), (8, 16, 8))


def prewarm_fuse(
    slice_buckets=(1, 2, 4, 8), shapes=_FUSE_SHAPES,
    reduces=("count", "total"),
) -> int:
    """Compile the multi-query interpreter's smallest geometry buckets
    (plan.compiled_interp — "count" for the mixed-storm hot path and
    "total" for the on-device-reduced Count storm).  The program is
    expression-INDEPENDENT (opcode tables are data), so these few
    compiles cover every query mix of their geometry."""
    warmed = 0
    for n_leaves, p_bucket, k_bucket in shapes:
        prog = np.zeros((p_bucket, 4), dtype=np.int32)
        out = np.zeros(k_bucket, dtype=np.int32)
        for n in slice_buckets:
            leaves = np.zeros(
                (n, n_leaves, bp.WORDS_PER_SLICE), dtype=np.uint32
            )
            for reduce in reduces:
                plan.interp_exec(
                    reduce, leaves, prog, out
                ).block_until_ready()
                warmed += 1
    return warmed


# Scorer programs every node warms: one fragment at the first two
# plane-row classes (a new index's first TopN).  A node that boots on
# loaded data also warms the programs ITS indexes will use
# (:func:`topn_shapes`, taken by the server before it listens).
_TOPN_SHAPES = ((1, bp.ROW_BLOCK), (1, 2 * bp.ROW_BLOCK))
# At most this many scorer programs are warmed from the holder's own
# shapes, the views with the most fragments first: each compiles for
# seconds, and a churny schema has dozens of (members, rows) classes.
_TOPN_SHAPES_MAX = 8


def _view_shapes(holder) -> dict[tuple[int, int, int, int], int]:
    """``(members, plane rows, block rows, row words)`` of the programs that read
    the holder's ranked views a home device at a time, each with the
    fragment count of the largest view that has it: the n fragments of
    a view that share a device are read ``bp.score_group_bucket(n)``
    members a launch whatever n is, at the pow2 row class of their
    planes and at the width they keep (``bp.row_words``), and a leaf
    batch over them is a block of ``plan.slice_bucket(n)`` rows."""
    n_dev = len(bp.participating_devices())
    weight: dict[tuple[int, int, int, int], int] = {}
    for idx in holder.indexes().values():
        for frame in idx.frames().values():
            for name, view in frame.views().items():
                # TopN ranks the rows of a standard or inverse view (time
                # views among them), never the bit planes of a BSI field.
                if not name.startswith((VIEW_STANDARD, VIEW_INVERSE)):
                    continue
                frags = view.fragments()
                if not frags:
                    continue
                n = -(-len(frags) // n_dev)
                for rows, words in {(f.plane_rows(), f.plane_words()) for f in frags}:
                    key = (bp.score_group_bucket(n), rows, plan.slice_bucket(n), words)
                    weight[key] = max(weight.get(key, 0), len(frags))
    return weight


def _heaviest(weight: dict) -> list:
    return sorted(weight, key=lambda k: -weight[k])[:_TOPN_SHAPES_MAX]


def topn_shapes(holder) -> list[tuple[int, int, int]]:
    """The ``(members, plane rows, row words)`` of the scorer programs
    the holder's indexes will use, the views with the most fragments
    first."""
    weight: dict[tuple[int, int, int], int] = {}
    for (members, rows, _, words), w in _view_shapes(holder).items():
        key = (members, rows, words)
        weight[key] = max(weight.get(key, 0), w)
    return _heaviest(weight)


# Leaves of the gather that is warmed from the holder's shapes: the
# two-row set algebra under a Count, the headline query.
_GATHER_LEAVES = 2


def gather_shapes(holder) -> list[tuple[int, int, int, int, int, int]]:
    """The ``(members, plane rows, block rows, k, leaves, row words)`` of the
    leaf-batch gather (``bp.gather_planes``) and of its in-place write
    over the holder's indexes, for a tree of ``_GATHER_LEAVES`` rows of
    one view, the views with the most fragments first."""
    return [
        shape[:3] + (_GATHER_LEAVES, _GATHER_LEAVES, shape[3])
        for shape in _heaviest(_view_shapes(holder))
    ]


def prewarm_gather(shapes=(), devices=None) -> int:
    """Compile the leaf-batch gather of a batch-cache miss at each
    ``(members, plane rows, block rows, k, leaves[, row words])`` of
    ``shapes`` — ``k`` rows of one view in a tree of ``leaves``, planes
    of full width unless said — and the in-place
    write of a launch's output into the block where a launch does not
    fill it.  The jit keys hold no expression and no slice count, so
    one warm-up serves every operator.  On each of ``devices`` (a
    compiled program is a device's own), side by side; as
    :func:`prewarm_topn`, the first device alone unless given."""
    import jax
    import jax.numpy as jnp

    def warm(dev, members, rows, block_rows, k, n_leaves, words=bp.WORDS_PER_SLICE):
        zero = jax.device_put(np.zeros((rows, words), dtype=np.uint32), dev)
        out = next(
            bp.gather_planes(
                [zero] * members,
                np.zeros((members, k), dtype=np.int32),
                first_call=plan.note_gather_first_call,
            )
        )
        if (block_rows, n_leaves, words) != (members, k, bp.WORDS_PER_SLICE):
            out = bp.place_rows(
                jnp.zeros(
                    (block_rows, n_leaves, bp.WORDS_PER_SLICE),
                    dtype=jnp.uint32,
                    device=dev,
                ),
                out,
                0,
                first_call=plan.note_gather_first_call,
            )
        out.block_until_ready()

    todo = [
        (dev,) + tuple(shape)
        for shape in shapes
        for dev in (devices or (bp.home_device(0),))
    ]
    threads = [threading.Thread(target=warm, args=t, daemon=True) for t in todo]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return len(todo)


def agg_shapes(holder) -> list[tuple]:
    """The ``(expr, cols, units, plane shape, members)`` of the in-place
    BSI aggregate (``bp.aggregate_planes``) for a plain ``Sum`` of each
    integer field the holder's indexes hold, the fields with the most
    fragments first.  The expression and the leaf layout are the
    executor's own (``Executor.bsi_agg_call``, ``_agg_columns``); the
    holder adds what the fragments decide of the program: the shape
    (row class, row words) of the field's planes, and ``bp.agg_members`` for the n fragments
    that share a device.  A filtered aggregate's program holds its
    filter tree and compiles on its first call.  No mirror is uploaded
    for it."""
    from pilosa_tpu.exec.executor import Executor  # it imports this module

    n_dev = len(bp.participating_devices())
    weight: dict[tuple, int] = {}
    for idx in holder.indexes().values():
        for name, frame in idx.frames().items():
            for fld in frame.bsi_fields():
                view = frame.view(fld.view)
                frags = view.fragments() if view is not None else []
                if not frags:
                    continue
                expr, leaves = plan.decompose(Executor.bsi_agg_call("Sum", name, fld))
                cols, units = Executor._agg_columns(leaves)
                n = -(-len(frags) // n_dev)
                k = sum(c[0] == "row" for c in cols)
                for shape in {(f.plane_rows(), f.plane_words()) for f in frags}:
                    key = (expr, cols, units, shape, bp.agg_members(n, shape[0] + k))
                    weight[key] = max(weight.get(key, 0), len(frags))
    return _heaviest(weight)


def prewarm_agg(shapes=(), devices=None) -> int:
    """Compile the in-place BSI aggregate at each ``(expr, cols, units,
    plane shape, members)`` of ``shapes`` (:func:`agg_shapes`), on each
    of ``devices`` (a compiled program is a device's own), side by
    side; the first device alone unless given."""
    import jax

    def warm(dev, expr, cols, units, shape, members):
        zero = jax.device_put(np.zeros(shape, dtype=np.uint32), dev)
        for out in bp.aggregate_planes(
            plan._eval_expr,
            expr,
            cols,
            units,
            [zero] * (members * len(units)),
            np.zeros((members, sum(c[0] == "row" for c in cols)), dtype=np.int32),
            np.zeros((0, bp.PRED_WORDS), dtype=np.uint32),
            first_call=plan.note_agg_first_call,
        ):
            out.block_until_ready()

    todo = [
        (dev,) + tuple(shape)
        for shape in shapes
        for dev in (devices or (bp.home_device(0),))
    ]
    threads = [threading.Thread(target=warm, args=t, daemon=True) for t in todo]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return len(todo)


def rows_shapes(holder) -> list[tuple[int, int]]:
    """The ``(plane rows, row words)`` of the walked scorer
    (``bp.score_rows``) the holder's indexes will use: a ranked view
    that is ONE fragment is scored by a walk of its plane, at the row
    class and the width the fragment keeps.  The fullest planes first."""
    weight: dict[tuple[int, int], int] = {}
    for idx in holder.indexes().values():
        for frame in idx.frames().values():
            for name, view in frame.views().items():
                if not name.startswith((VIEW_STANDARD, VIEW_INVERSE)):
                    continue
                frags = view.fragments()
                if len(frags) == 1:
                    key = (frags[0].plane_rows(), frags[0].plane_words())
                    weight[key] = max(weight.get(key, 0), frags[0].plane_nbytes)
    return _heaviest(weight)


def prewarm_rows(shapes=()) -> int:
    """Compile the walked TopN scorer at each ``(plane rows, row
    words)`` of ``shapes`` (:func:`rows_shapes`), placed as slice 0's
    mirror is."""
    import jax

    dev = bp.home_device(0)
    for rows, words in shapes:
        outs = bp.score_rows(
            jax.device_put(np.zeros((rows, words), dtype=np.uint32), dev),
            jax.device_put(np.zeros(rows, dtype=np.int32), dev),
            0, 0, 0, 0,
            first_call=plan.note_scorer_first_call,
        )
        outs[0].block_until_ready()
    return len(shapes)


def prewarm_topn(shapes=_TOPN_SHAPES) -> int:
    """Compile the fused TopN scorer — the self-src variant of
    ``bp.score_planes`` (the common ``TopN(Bitmap(frame=f), frame=f)``
    shape) — at each ``(members, plane rows[, row words])`` of
    ``shapes`` (full-width planes unless said), with as many candidate
    slots as plane rows (every row a candidate).  Every
    dimension of the scorer's jit key is pow2-bucketed and the member
    count is bounded by ``bp.SCORE_GROUP`` (ops/bitplane.py), so these
    are exactly the programs the first TopN queries hit, however many
    slices the index has.  On a multi-device host this warms the first
    device's programs; the others load theirs from the persistent
    cache on first use."""
    import jax

    warmed = 0
    for members, rows, *width in shapes:
        # Placed as a fragment's mirror is (the jit key holds the
        # placement): slice 0's home device.
        zero = jax.device_put(
            np.zeros((rows, *(width or (bp.WORDS_PER_SLICE,))), dtype=np.uint32),
            bp.home_device(0),
        )
        planes = [zero] * members
        slots = np.zeros((members, rows), dtype=np.int32)
        src_slots = np.zeros(members, dtype=np.int32)
        for out in bp.score_planes(
            planes, slots, src_slots=src_slots, first_call=plan.note_scorer_first_call
        ):
            out.block_until_ready()
        warmed += 1
    return warmed


def prewarm(
    buckets=(1, 2, 4, 8), exprs=_STANDARD_EXPRS, coalesce=False, topn=(),
    gather=(), agg=(), rows=(),
) -> int:
    """Compile the standard (tree shape x slice bucket) programs, the
    TopN scorer at its standard shapes and at ``topn`` (the ``(members,
    plane rows)`` of :func:`topn_shapes`), the leaf-batch gather at
    ``gather`` (:func:`gather_shapes`) and the in-place BSI aggregate at
    ``agg`` (:func:`agg_shapes`) and the walked TopN scorer at ``rows``
    (:func:`rows_shapes`).

    Triggers real compilations by calling each program on a zero batch
    of the bucketed shape — with the persistent cache enabled this both
    fills the in-process jit cache and writes the executables to disk.
    Covers the same jit keys the executor hits (executor.py:687-770):
    single-device count AND row reduces at every bucket (row queries
    evaluate over the whole power-of-two batch, not per slice), and on
    a multi-device host the MESH variants too — sharded-input keys
    differ from the single-device ones, so each must warm on its own.
    Returns the number of programs warmed.  Safe to run concurrently
    with serving: jit compilation is thread-safe and zero inputs are
    discarded.
    """
    import jax

    from pilosa_tpu.parallel import mesh as pmesh

    mesh = pmesh.default_slices_mesh()
    warmed = 0
    for expr in exprs:
        nl = _n_leaves(expr)
        for bucket in buckets:
            batch = np.zeros((bucket, nl, bp.WORDS_PER_SLICE), dtype=np.uint32)
            plan.compiled_total_count(expr)(batch).block_until_ready()
            plan.compiled_batched(expr, "row")(batch).block_until_ready()
            warmed += 2
        if mesh is not None:
            # First queries over >1 slice on a mesh host: per-device
            # chunk 1 covers up to n_devices slices, chunk 2 to 2x.
            for chunk in (1, 2):
                blocks = [
                    jax.device_put(
                        np.zeros(
                            (chunk, nl, bp.WORDS_PER_SLICE), dtype=np.uint32
                        ),
                        d,
                    )
                    for d in mesh.devices.flat
                ]
                batch = pmesh.assemble_sharded_batch(blocks, mesh)
                # No compiled_batched(expr, "count") here: the executor
                # only takes that fallback past the 2^15-partial budget
                # (executor.py:758), never at these chunk sizes.
                plan.compiled_total_count(expr, mesh)(batch).block_until_ready()
                plan.compiled_batched(expr, "row")(batch).block_until_ready()
                warmed += 2
    warmed += prewarm_topn(
        list(_TOPN_SHAPES) + [k for k in topn if k not in _TOPN_SHAPES]
    )
    warmed += prewarm_gather(gather)
    warmed += prewarm_agg(agg)
    warmed += prewarm_rows(rows)
    if coalesce:
        warmed += prewarm_coalesce()
        warmed += prewarm_fuse()
    return warmed


def prewarm_async(
    logger=None, coalesce=False, topn=(), gather=(), agg=(), rows=()
) -> threading.Thread:
    """Run :func:`prewarm` on a daemon thread (server open must not
    block on compiles) and return the thread, which carries the
    outcome once it ends: ``programs`` (the count compiled) or
    ``error`` (the exception that stopped it — a standard program that
    cannot compile here; ``GET /debug/health`` shows it)."""

    def run():
        try:
            t.programs = prewarm(
                coalesce=coalesce, topn=topn, gather=gather, agg=agg, rows=rows
            )
        except Exception as e:  # noqa: BLE001 — recorded, not swallowed
            t.error = e
            if logger is not None:
                logger(f"prewarm failed: {e!r}")
            return
        if logger is not None:
            logger(f"prewarm: {t.programs} standard query programs compiled")

    t = threading.Thread(target=run, daemon=True, name="prewarm")
    t.programs = None
    t.error = None
    t.start()
    return t
