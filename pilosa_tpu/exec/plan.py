"""Query planning: PQL call trees -> fused XLA programs.

The reference interprets a call tree per slice, materializing a roaring
bitmap at every node and dispatching per-container merge kernels
(reference: executor.go:263-278 executeBitmapCallSlice and the roaring
kernels under it).  On TPU that structure would bounce every intermediate
through HBM; instead each *tree shape* compiles once to a single jitted
function over a stack of leaf rows:

    Count(Intersect(Bitmap(a), Bitmap(b)))
      -> fn(leaves: uint32[2, 32768]) = popcount_sum(leaves[0] & leaves[1])

XLA fuses the whole expression (bitwise ops + popcount + reduce) into one
kernel, so no intermediate row ever materializes.  Shapes are static:
every leaf is one slice-row (32768 uint32 words), so one compilation per
(tree-shape, reduce-kind) serves every slice and every rowID — query
shape bucketing per SURVEY.md §7 "dynamic shapes".

Leaf calls are ``Bitmap`` and ``Range`` (row fetches); interior calls are
``Intersect``/``Union``/``Difference`` (left-fold, reference:
executor.go:418-434,486-505,621-637).
"""

from __future__ import annotations

import functools
import threading
import time
from collections import OrderedDict, namedtuple
from collections.abc import Callable

import jax
import jax.numpy as jnp

from pilosa_tpu.bsi import ripple
from pilosa_tpu.obs import trace
from pilosa_tpu.pql.parser import WRITE_CALLS, Call

# Calls that fetch rows (leaves of a bitmap expression).  The Bsi*
# leaves are synthetic calls the executor's BSI rewrite produces:
# BsiPlane fetches one field-view plane row, BsiPred is a packed
# predicate row (slice-invariant data), BsiZero an all-zero pad plane
# (depth bucketing).
LEAF_CALLS = frozenset({"Bitmap", "Range", "BsiPlane", "BsiPred", "BsiZero"})
# Interior set-algebra calls and their fold ops.
FOLD_CALLS = frozenset({"Intersect", "Union", "Difference", "Xor"})
# Synthetic BSI interior calls (executor._rewrite_bsi / _rewrite_bsi_agg):
# BsiCmp produces a result row (composable inside bitmap trees); the
# aggregates produce per-slice int32 partial vectors (reduce "agg").
BSI_CALLS = frozenset({"BsiCmp", "BsiSum", "BsiMin", "BsiMax"})
# Leaves that carry slice-invariant data rather than fragment content:
# they never make a slice non-empty on their own.
NEUTRAL_LEAVES = frozenset({"BsiPred", "BsiZero"})


class PlanError(ValueError):
    pass


def decompose(call: Call) -> tuple[tuple, list[Call]]:
    """Flatten a bitmap call tree into a hashable structure + leaf calls.

    Returns ``(expr, leaves)`` where ``expr`` is a nested tuple — ``("leaf",
    i)`` referencing ``leaves[i]``, or ``(op, child_exprs...)`` — usable as
    a jit cache key.
    """
    leaves: list[Call] = []

    def rec(c: Call) -> tuple:
        if c.name in LEAF_CALLS:
            idx = len(leaves)
            leaves.append(c)
            return ("leaf", idx)
        if c.name in BSI_CALLS:
            # Statics come from the synthetic call's args; depth is
            # implied by the child arity, so fields sharing a depth
            # bucket share one expr (and one compiled program) per op.
            if c.name == "BsiCmp":
                head = ("bsiCmp", c.args["op"])
            else:
                tag = {"BsiSum": "bsiSum", "BsiMin": "bsiMin", "BsiMax": "bsiMax"}
                head = (tag[c.name], bool(c.args.get("filter")))
            return head + tuple(rec(ch) for ch in c.children)
        if c.name not in FOLD_CALLS:
            raise PlanError(f"unknown call: {c.name}")
        if c.name in ("Intersect", "Difference") and not c.children:
            raise PlanError(f"empty {c.name} query is currently not supported")
        return (c.name,) + tuple(rec(ch) for ch in c.children)

    return rec(call), leaves


def collect_leaf_calls(call: Call) -> list[Call]:
    """Every Bitmap/Range leaf reachable under ``call``, crossing
    non-bitmap wrappers (Count's child, TopN's src tree) — the
    prefetcher's walk (device/prefetch.py).  Unlike :func:`decompose`
    it never raises on unknown interior calls: the prefetcher only
    needs the leaves' (frame, view, row) identities to re-materialize
    cold mirrors, not a valid bitmap expression, so anything
    unrecognized just recurses into its children."""
    out: list[Call] = []

    def rec(c: Call) -> None:
        if c.name in LEAF_CALLS:
            out.append(c)
            return
        for ch in c.children:
            rec(ch)

    rec(call)
    return out


# ---------------------------------------------------------------------------
# cost classes (net/admission.py): the admission layer's view of a plan
# ---------------------------------------------------------------------------

COST_POINT = "point"
COST_HEAVY = "heavy"
COST_WRITE = "write"

# Calls whose execution fans past a single fused row program: TopN's
# two-phase candidate walk, the BSI aggregates' per-slice partial
# vectors, and Range (time-view union / ~depth-many plane leaves per
# BSI comparison) all cost an order of magnitude more device and host
# work per slice than a point Count/Bitmap tree.
_HEAVY_CALLS = frozenset({"TopN", "Sum", "Min", "Max", "Range"})


def cost_class(calls: "list[Call]") -> str:
    """The admission cost class of a parsed query: ``write`` when any
    call mutates, else ``heavy`` when any call (at any depth) is a
    TopN/aggregate/Range, else ``point``.  Derived purely from the
    parsed plan — classification must stay cheap enough to run before
    any admission decision, let alone device work."""

    def heavy(c: Call) -> bool:
        if c.name in _HEAVY_CALLS:
            return True
        return any(heavy(ch) for ch in c.children)

    if any(c.name in WRITE_CALLS for c in calls):
        return COST_WRITE
    if any(heavy(c) for c in calls):
        return COST_HEAVY
    return COST_POINT


def canonicalize_call(c: Call) -> Call:
    """Reorder the children of commutative fold calls (Intersect/
    Union/Xor — AND/OR/XOR on bitsets) into a canonical order, bottom
    up, so semantically identical trees that differ only in argument
    ordering produce one canonical string (``str(call)`` already sorts
    keyword args).  This is the compile-key canonicalization the
    single-flighted TopN score cache keys through: without it,
    ``TopN(Intersect(A, B), ...)`` and ``TopN(Intersect(B, A), ...)``
    each paid their own dispatch+fetch.  Returns the ORIGINAL object
    when nothing changed.  Difference is not commutative and is left
    alone; results are byte-identical either way."""
    kids = [canonicalize_call(ch) for ch in c.children]
    if c.name in ("Intersect", "Union", "Xor") and len(kids) > 1:
        kids = sorted(kids, key=str)
    if len(kids) == len(c.children) and all(
        a is b for a, b in zip(kids, c.children)
    ):
        return c
    return Call(name=c.name, args=dict(c.args), children=kids)


def _popcount32(row):
    return jnp.sum(jax.lax.population_count(row).astype(jnp.int32))


def _split_bsi_rows(rows, tail: int):
    """(exists, sign, planes, tail_rows) from a BSI node's evaluated
    children — ``tail`` trailing rows are predicate/filter rows."""
    body = rows[: len(rows) - tail] if tail else rows
    return body[0], body[1], body[2:], rows[len(rows) - tail :]


def _eval_expr(expr: tuple, leaves):
    if expr[0] == "leaf":
        return leaves[expr[1]]
    name = expr[0]
    if name == "bsiCmp":
        op = expr[1]
        rows = [_eval_expr(e, leaves) for e in expr[2:]]
        npred = 2 if op == "between" else 1
        exists, sign, planes, preds = _split_bsi_rows(rows, npred)
        if op == "between":
            return ripple.between_row(
                exists, sign, planes, preds[0], preds[1], jnp
            )
        return ripple.signed_cmp(op, exists, sign, planes, preds[0], jnp)
    if name in ("bsiSum", "bsiMin", "bsiMax"):
        has_filter = expr[1]
        rows = [_eval_expr(e, leaves) for e in expr[2:]]
        exists, sign, planes, tail = _split_bsi_rows(
            rows, 1 if has_filter else 0
        )
        filt = tail[0] if has_filter else None
        if name == "bsiSum":
            return ripple.sum_vec(exists, sign, planes, filt, jnp, _popcount32)
        return ripple.minmax_vec(
            "min" if name == "bsiMin" else "max",
            exists, sign, planes, filt, jnp, _popcount32, jnp.where,
        )
    children = [_eval_expr(e, leaves) for e in expr[1:]]
    if name == "Union" and not children:
        return jnp.zeros(leaves.shape[1:], dtype=leaves.dtype)
    acc = children[0]
    for nxt in children[1:]:
        if name == "Intersect":
            acc = acc & nxt
        elif name == "Union":
            acc = acc | nxt
        elif name == "Difference":
            acc = acc & ~nxt
        elif name == "Xor":
            acc = acc ^ nxt
    return acc


def eval_expr_np(expr: tuple, leaf_rows, words: int):
    """HOST (numpy) evaluation of a decomposed tree over one slice's
    leaf rows (``leaf_rows[i]`` is uint32[words] or None = empty).

    The fold ops are the same as the device _eval_expr; numpy vectorizes
    them in one pass over 128 KiB, which beats a device dispatch for the
    side computations that feed host logic (e.g. the TopN src row: its
    consumer needs host words for sparse probing, so evaluating on
    device would add a dispatch and a blocking fetch before the host
    work could start)."""
    import numpy as np

    def rec(e):
        if e[0] == "leaf":
            r = leaf_rows[e[1]]
            return None if r is None else np.asarray(r, dtype=np.uint32)
        name = e[0]
        if name in ("bsiCmp", "bsiSum", "bsiMin", "bsiMax"):
            rows = [rec(c) for c in e[2:]]
            rows = [
                np.zeros(words, dtype=np.uint32) if r is None else r
                for r in rows
            ]
            pops = lambda r: int(np.bitwise_count(r).sum()) if hasattr(  # noqa: E731
                np, "bitwise_count"
            ) else int(np.unpackbits(r.view(np.uint8)).sum())
            if name == "bsiCmp":
                op = e[1]
                npred = 2 if op == "between" else 1
                exists, sign, planes, preds = _split_bsi_rows(rows, npred)
                if op == "between":
                    return ripple.between_row(
                        exists, sign, planes, preds[0], preds[1], np
                    )
                return ripple.signed_cmp(op, exists, sign, planes, preds[0], np)
            has_filter = e[1]
            exists, sign, planes, tail = _split_bsi_rows(
                rows, 1 if has_filter else 0
            )
            filt = tail[0] if has_filter else None
            if name == "bsiSum":
                return ripple.sum_vec(exists, sign, planes, filt, np, pops)
            return ripple.minmax_vec(
                "min" if name == "bsiMin" else "max",
                exists, sign, planes, filt, np, pops, np.where,
            )
        children = [rec(c) for c in e[1:]]
        zeros = lambda: np.zeros(words, dtype=np.uint32)  # noqa: E731
        if name == "Union":
            live = [c for c in children if c is not None]
            if not live:
                return None
            acc = live[0]
            for nxt in live[1:]:
                acc = acc | nxt
            return acc
        acc = children[0]
        for nxt in children[1:]:
            if name == "Intersect":
                if acc is None or nxt is None:
                    return None
                acc = acc & nxt
            elif name == "Difference":
                if acc is None:
                    return None
                if nxt is not None:
                    acc = acc & ~nxt
            elif name == "Xor":
                if acc is None:
                    acc = zeros()
                acc = acc ^ (nxt if nxt is not None else zeros())
        return acc

    return rec(expr)


def _make_fn(expr: tuple, reduce: str):
    """``reduce``: ``"row"`` returns the uint32[32768] result row;
    ``"count"`` returns the int32 popcount of the result (never
    materializing it); ``"agg"`` passes the expression's own int32
    partial vector through unchanged (the BSI aggregate nodes reduce
    inside the expression)."""

    def fn(leaf_stack):
        out = _eval_expr(expr, leaf_stack)
        if reduce == "count":
            return jnp.sum(jax.lax.population_count(out).astype(jnp.int32))
        return out

    return fn


def compiled_batched(expr: tuple, reduce: str) -> "_Program":
    """One jitted program per (tree shape, reduce kind), vmapped over a
    leading slice axis — input uint32[n_slices, n_leaves, 32768].  All of
    a node's local slices evaluate in ONE device program (the TPU-shaped
    equivalent of the reference's goroutine-per-slice mapperLocal,
    reference: executor.go:1246-1282).

    XLA emits the whole expression as one fused bitwise+popcount+reduce
    pass; there is no hand-written kernel (its speed on the chip is not
    measured on the current code, see PERF.md)."""
    return _compiled_batched(expr, reduce)


# On-device count reduce budget, in PARTIALS (one partial = one
# slice-row's popcount, <= 2^20 bits).  TPUs have no native int64, so
# the reduce runs TWO-STAGE in 16-bit limbs of the per-slice-row int32
# partials: sum(partial & 0xFFFF) stays below 2^31 for up to 2^15
# partials and sum(partial >> 16) far longer; the host recombines
# hi*2^16 + lo in Python ints.  2^15 single-row slices = ~34B columns
# per node — past BASELINE configs[4]'s 10B-column cluster shape.
# Callers fall back to the per-slice host sum (int64) beyond this.
MAX_ONDEVICE_COUNT_PARTIALS = 1 << 15


def compiled_total_count(expr: tuple, mesh=None) -> "_Program":
    """Count(tree) reduced to one replicated int32[2] = (hi, lo) limb
    pair on-device; total = (hi << 16) + lo, recombined by the caller
    (recombine_count_limbs).  ``mesh=None`` compiles the single-device
    variant: same limb math, no collective — only 8 bytes return to the
    host instead of a per-slice partial vector.

    Input: uint32[n_slices, n_leaves, *rest, words] sharded P(slices,
    None, ...) over ``mesh``.  The word axis reduces first — every
    partial covers at most one slice-row's 2^20 bits, so int32 is exact
    — then the partials limb-split and sum across ALL remaining axes
    *inside* the jitted program, so the SPMD partitioner inserts the
    cross-device all-reduce (psum riding ICI) — the collective
    replacement for the reference's streaming HTTP fan-in reduce
    (reference: executor.go:1176-1207).  Only the two scalars ever
    reach the host, and the limb math is exact for up to
    MAX_ONDEVICE_COUNT_PARTIALS slice-row partials.
    """
    return _compiled_total_count(expr, mesh)


# Collective-bearing launches (programs whose cross-slice reduce psums
# over a sharded mesh axis) must never be IN FLIGHT concurrently from
# two threads of one process: each launch enqueues on every
# participating device, and two racing dispatches can enqueue in
# different per-device orders — both all-reduces then wait forever for
# participants stuck behind the other program (observed as the CPU
# backend's cross_module rendezvous stall; the hazard is structural,
# not backend-specific).  One process-wide mutex serializes them:
# collective programs occupy the whole mesh anyway, so the lock costs
# nothing a real device would not already charge.  Collective-free
# launches (vmapped per-slice programs, single-device reduces) never
# take it.
_collective_mu = threading.Lock()


def collective_launch() -> "threading.Lock":
    """The process-wide mesh-collective launch lock; hold it across
    dispatch + fetch of any program compiled with a mesh psum
    (compiled_total_count(expr, mesh), interp "total" on sharded input,
    parallel/mesh's distributed reduces)."""
    return _collective_mu


def recombine_count_limbs(limbs):
    """(hi, lo) int32 limbs -> exact totals.

    Scalar limb pair (shape [2]) -> Python int; vector limbs (shape
    [2, n]) -> int64 ndarray.  The single recombination point for every
    limb-split device reduce (Count and TopN)."""
    import numpy as np

    limbs = np.asarray(limbs, dtype=np.int64)
    hi, lo = limbs[0], limbs[1]
    total = (hi << 16) + lo
    return int(total) if total.ndim == 0 else total


def expr_has_bsi(expr: tuple) -> bool:
    """Whether a decomposed expr contains a BSI node.  BSI nodes index
    WORDS of their predicate row and reduce internally, so they must
    evaluate per slice (vmap) — the leaf-major broadcast trick the pure
    bitwise total-count uses would hand them whole slice axes."""
    if expr[0] == "leaf":
        return False
    if expr[0] in ("bsiCmp", "bsiSum", "bsiMin", "bsiMax"):
        return True
    return any(expr_has_bsi(e) for e in expr[1:])


def slice_bucket(n: int) -> int:
    """Canonical pow2 bucket for a batch's leading slice axis — the ONE
    bucketing rule every batch assembler (executor, coalescer, warmup)
    must use, so their launches land on the same compiled programs."""
    from pilosa_tpu.ops import bitplane as bp

    return bp.pow2_bucket(n, 1)


# ---------------------------------------------------------------------------
# expression-as-data interpreter (plane-major multi-query fusion)
# ---------------------------------------------------------------------------
#
# ``compiled_batched`` compiles one program per TREE SHAPE, so a mix of
# DISTINCT concurrent queries never shares a launch and each re-streams
# its resident planes.  The interpreter generalizes the PR-6
# predicates-travel-as-data idiom (bsi.pred_row) to the expression
# itself: a register machine whose opcode/operand table is an ordinary
# int32 INPUT — K distinct trees lower to one table, the compiled
# program streams the union leaf set exactly once per dispatch, and a
# new query is a new table row, NEVER a recompile.  The jit key is pure
# geometry — (slice bucket, leaf bucket, op bucket, out bucket, reduce)
# — every axis pow2-bucketed, so the family's compiled-entry count is
# O(1) in concurrent-mix diversity (program_cache_bounds "interp").
#
# Register file layout per slice: slots [0, n_leaves) are the stacked
# leaf rows, slot n_leaves + i is instruction i's output.  Instruction
# row: (opcode, a, b, aux).

OP_AND = 0
OP_OR = 1
OP_ANDNOT = 2
OP_XOR = 3
# Broadcast of predicate word ``aux`` of register ``a``: all-ones iff
# bit 0 of that word is set — the BSI ripple's per-plane predicate mask
# (ripple.lower_magnitude_cmp), reading the packed bsi.pred_row leaf.
OP_MASKW = 4

# Opcode-table budget for one fused launch: a lowered tree past this
# falls back to the per-compile-key coalesce path (its own concat
# launch) rather than splintering the bucket grid.  Tables pad to pow2
# buckets >= FUSE_OPS_FLOOR.
FUSE_MAX_OPS = 256
FUSE_OPS_FLOOR = 8


class FuseUnsupported(PlanError):
    """The expression cannot lower to the interpreter's opcode table
    (BSI aggregates reduce inside the expression; oversized trees blow
    the op budget) — callers fall back to the per-compile-key path."""


class FuseEmitter:
    """Value-numbering opcode emitter: identical instructions (with
    commutative operand order normalized) share one register, so
    shared subtrees within a fused batch evaluate once.  ``rollback``
    restores a checkpoint when a tree fails to lower mid-way, keeping
    the shared table clean for the batch's other queries."""

    def __init__(self, n_leaves: int, max_ops: int = FUSE_MAX_OPS):
        self.n_leaves = int(n_leaves)
        self.max_ops = int(max_ops)
        self.rows: list[tuple[int, int, int, int]] = []
        self._memo: dict[tuple, int] = {}
        self.dedup_hits = 0

    def _emit(self, op: int, a: int, b: int, aux: int = 0) -> int:
        if op in (OP_AND, OP_OR, OP_XOR) and b < a:
            a, b = b, a
        key = (op, a, b, aux)
        reg = self._memo.get(key)
        if reg is not None:
            self.dedup_hits += 1
            return reg
        if len(self.rows) >= self.max_ops:
            raise FuseUnsupported(
                f"opcode table full ({self.max_ops} instructions)"
            )
        reg = self.n_leaves + len(self.rows)
        self.rows.append((int(op), int(a), int(b), int(aux)))
        self._memo[key] = reg
        return reg

    def and_(self, a: int, b: int) -> int:
        return self._emit(OP_AND, a, b)

    def or_(self, a: int, b: int) -> int:
        return self._emit(OP_OR, a, b)

    def andnot(self, a: int, b: int) -> int:
        return self._emit(OP_ANDNOT, a, b)

    def xor(self, a: int, b: int) -> int:
        return self._emit(OP_XOR, a, b)

    def maskw(self, a: int, word: int) -> int:
        return self._emit(OP_MASKW, a, a, word)

    def checkpoint(self) -> tuple:
        return len(self.rows), dict(self._memo), self.dedup_hits

    def rollback(self, cp: tuple) -> None:
        n, memo, hits = cp
        del self.rows[n:]
        self._memo = memo
        self.dedup_hits = hits


_FOLD_EMIT = {
    "Intersect": "and_",
    "Union": "or_",
    "Difference": "andnot",
    "Xor": "xor",
}


def _leaf_reg(leaf_map, i: int) -> int:
    return leaf_map + i if isinstance(leaf_map, int) else leaf_map[i]


def lower_expr(expr: tuple, leaf_map, em: FuseEmitter) -> int:
    """Lower one decomposed tree into ``em``'s opcode table; returns
    the result row's register id.  ``leaf_map`` places the tree's
    leaves in the combined register file: an int means leaves sit
    contiguously at ``base + i``; a sequence maps leaf ordinal ``i`` to
    its register — the fused union-leaf layout, where leaf columns
    SHARED between queries (same fragment row, same slice geometry)
    collapse to one register, so the emitter's value numbering dedups
    whole subtrees across distinct queries.  The emitted stream mirrors
    :func:`_eval_expr` operation for operation (the BSI ripple lowers
    through bsi/ripple.py's ``lower_*``), so interpreter results are
    byte-identical to the direct compiled tree.  Raises
    :class:`FuseUnsupported` for BSI aggregates (they reduce inside the
    expression) and when the op budget runs out."""
    if expr[0] == "leaf":
        return _leaf_reg(leaf_map, expr[1])
    name = expr[0]
    if name == "bsiCmp":
        op = expr[1]
        regs = [lower_expr(e, leaf_map, em) for e in expr[2:]]
        npred = 2 if op == "between" else 1
        body, preds = regs[: len(regs) - npred], regs[len(regs) - npred :]
        exists, sign, planes = body[0], body[1], body[2:]
        if op == "between":
            return ripple.lower_between(
                em, exists, sign, planes, preds[0], preds[1]
            )
        return ripple.lower_signed_cmp(em, op, exists, sign, planes, preds[0])
    if name in ("bsiSum", "bsiMin", "bsiMax"):
        raise FuseUnsupported(f"{name} reduces inside the expression")
    children = [lower_expr(e, leaf_map, em) for e in expr[1:]]
    if not children:
        # Empty Union: the canonical all-zero row (x ^ x).
        zero = _leaf_reg(leaf_map, 0)
        return em.xor(zero, zero)
    emit = getattr(em, _FOLD_EMIT[name])
    acc = children[0]
    for nxt in children[1:]:
        acc = emit(acc, nxt)
    return acc


def _build_interp(reduce: str):
    """One jitted interpreter per reduce kind: ``fn(leaves, prog,
    out_idx)`` with ``leaves`` uint32[n_slices, n_leaves, words],
    ``prog`` int32[n_ops, 4] instruction rows, ``out_idx`` int32[k]
    result-register selections.  A lax.scan threads the register file
    through the table (dynamic_update_index keeps the carry in place),
    vmapped over slices; ``"count"`` returns int32[n_slices, k]
    popcount partials, ``"row"`` uint32[n_slices, k, words] result
    rows, ``"total"`` int32[2, k] per-register (hi, lo) 16-bit limb
    pairs — the per-slice count partials limb-split and summed across
    the slice axis INSIDE the jitted program, so on a mesh-sharded
    batch the SPMD partitioner inserts the cross-device all-reduce
    (psum over ICI) and only 8·k bytes ever reach the host (exact up
    to MAX_ONDEVICE_COUNT_PARTIALS slice-row partials; zero pad slices
    contribute nothing to either limb).  The table and selections are
    DATA — one compiled entry per geometry bucket serves every
    expression mix."""
    inner = "count" if reduce == "total" else reduce

    def fn(leaves, prog, out_idx):
        n_leaves = leaves.shape[1]
        steps = prog.shape[0]

        def one(stack):
            regs0 = jnp.concatenate(
                [stack, jnp.zeros((steps, stack.shape[1]), dtype=stack.dtype)],
                axis=0,
            )

            def step(regs, x):
                row, i = x
                op, a, b, aux = row[0], row[1], row[2], row[3]
                ra = regs[a]
                rb = regs[b]
                val = jax.lax.switch(
                    op,
                    (
                        lambda ra, rb, aux: ra & rb,
                        lambda ra, rb, aux: ra | rb,
                        lambda ra, rb, aux: ra & ~rb,
                        lambda ra, rb, aux: ra ^ rb,
                        lambda ra, rb, aux: jnp.broadcast_to(
                            (ra[aux] & jnp.uint32(1))
                            * jnp.uint32(0xFFFFFFFF),
                            ra.shape,
                        ),
                    ),
                    ra,
                    rb,
                    aux,
                )
                return (
                    jax.lax.dynamic_update_index_in_dim(
                        regs, val, n_leaves + i, 0
                    ),
                    None,
                )

            regs, _ = jax.lax.scan(step, regs0, (prog, jnp.arange(steps)))
            outs = regs[out_idx]
            if inner == "count":
                return jnp.sum(
                    jax.lax.population_count(outs).astype(jnp.int32), axis=-1
                )
            return outs

        res = jax.vmap(one)(leaves)
        if reduce == "total":
            # Limb-split BEFORE the slice-axis sum (TPUs have no int64):
            # each partial <= 2^20, so lo/hi stay int32-exact up to 2^15
            # non-zero partials; the host recombines hi*2^16 + lo.  On
            # sharded input the sums become all-reduces over the mesh.
            lo = jnp.sum(res & 0xFFFF, axis=0)
            hi = jnp.sum(res >> 16, axis=0)
            return jnp.stack([hi, lo])
        return res

    return jax.jit(fn)


def compiled_interp(reduce: str) -> "_Program":
    """The interpreter program for one reduce kind ("count" | "row" |
    "total").  Callers bucket EVERY input axis to powers of two (coalescer
    _launch_interp / warmup.prewarm_fuse) — the compiled-entry count
    per wrapper is the product of the bucket grids, not the number of
    distinct expression mixes ever fused."""
    return _compiled_interp(reduce)


# Largest bucketed (leaf, op, out) axes ever dispatched — with the
# leading slice axis in _BUCKET_HIGHWATER["interp"], these derive the
# interp family's hard cardinality bound.  Plain dict writes: racing
# writers both store valid maxima.
_INTERP_HIGHWATER: dict[str, int] = {}


def interp_exec(reduce: str, leaves, prog, out_idx):
    """Dispatch one fused interpreter launch, recording the bucket
    high-waters the ``exec.programCache.bound[cache:interp]`` gauge
    derives from.  ``prog``/``out_idx`` may be host numpy — they are
    kilobytes of metadata riding the launch."""
    for k, v in (
        ("leaves", int(leaves.shape[1])),
        ("ops", int(prog.shape[0])),
        ("outs", int(out_idx.shape[0])),
    ):
        if v > _INTERP_HIGHWATER.get(k, 0):
            _INTERP_HIGHWATER[k] = v
    return _compiled_interp(reduce)(leaves, prog, out_idx)


class _Program:
    """Recording proxy around one jitted wrapper: records the bucketed
    leading batch axis at call time (feeding the hard-bound gauges) and
    passes ``lower`` through for AOT compile probes.  The underlying
    jit wrapper compiles once per distinct batch shape — with callers
    bucketing the slice axis to powers of two, a wrapper's compiled
    entry count is bounded by the bucket-class count, not by how many
    distinct slice sets queries touch.

    Compile-time accounting: jit compiles lazily at the first call per
    argument-shape tuple, so that FIRST call's wall time (trace + XLA
    compile + the dispatch itself) accrues to the family's cumulative
    ``exec.programCache.compileMs[cache:*]`` gauge — the online answer
    to "how much of this soak went to compilation" (a persistent-cache
    hit shows up as a near-zero first call)."""

    __slots__ = ("fn", "family", "_seen_shapes")

    def __init__(self, fn, family: str):
        self.fn = fn
        self.family = family
        self._seen_shapes: set = set()

    def __call__(self, batch, *args):
        _note_bucket(self.family, int(batch.shape[0]))
        shapes = (tuple(batch.shape),) + tuple(
            tuple(getattr(a, "shape", ())) for a in args
        )
        if shapes in self._seen_shapes:
            return self.fn(batch, *args)
        start, t0 = time.time(), time.monotonic()
        out = self.fn(batch, *args)
        ms = (time.monotonic() - t0) * 1e3
        # Unlocked set add + dict accumulate: a racing duplicate first
        # call double-counts a few ms of telemetry, never corrupts.
        self._seen_shapes.add(shapes)
        note_first_call(self.family, str(shapes), start, ms)
        return out

    def lower(self, *args, **kwargs):
        return self.fn.lower(*args, **kwargs)


CacheInfo = namedtuple("CacheInfo", ["hits", "misses", "maxsize", "currsize"])


class _ProgramCache:
    """Bounded memo of jit wrappers keyed by compile statics, with
    ``cache_info()`` compatible with the functools.lru_cache interface
    it replaces — replaced so :func:`program_cache_stats` can walk the
    live wrappers and count their COMPILED entries (an lru_cache hides
    its values).  Eviction past ``maxsize`` drops the oldest wrapper
    (and with it, its compiled executables)."""

    def __init__(self, builder: Callable, family: str, maxsize: int = 512):
        self._builder = builder
        self._family = family
        self._maxsize = maxsize
        self._d: "OrderedDict[tuple, _Program]" = OrderedDict()
        self._mu = threading.Lock()
        self._hits = 0
        self._misses = 0

    def __call__(self, *key) -> _Program:
        with self._mu:
            prog = self._d.get(key)
            if prog is not None:
                self._hits += 1
                return prog
            self._misses += 1
        fn = self._builder(*key)
        prog = _Program(fn, self._family)
        with self._mu:
            cur = self._d.setdefault(key, prog)
            while len(self._d) > self._maxsize:
                self._d.popitem(last=False)
            return cur

    def cache_info(self) -> CacheInfo:
        with self._mu:
            return CacheInfo(self._hits, self._misses, self._maxsize, len(self._d))

    def cache_clear(self) -> None:
        with self._mu:
            progs = list(self._d.values())
            self._d.clear()
            self._hits = self._misses = 0
        for p in progs:
            p.fn.clear_cache()

    def programs(self) -> list[_Program]:
        with self._mu:
            return list(self._d.values())


def _build_total_count(expr: tuple, mesh):
    per_slice = expr_has_bsi(expr)

    def fn(batch):
        if per_slice:
            # Per-slice evaluation (vmapped): each partial covers one
            # slice-row result (<= 2^20 bits), int32-exact.
            partials = jax.vmap(
                lambda stack: jnp.sum(
                    jax.lax.population_count(
                        _eval_expr(expr, stack)
                    ).astype(jnp.int32)
                )
            )(batch)
        else:
            out = _eval_expr(expr, batch.swapaxes(0, 1))
            # Word axis first: each partial <= 2^20 bits, int32-exact.
            partials = jnp.sum(
                jax.lax.population_count(out).astype(jnp.int32), axis=-1
            )
        lo = jnp.sum(partials & 0xFFFF)
        hi = jnp.sum(partials >> 16)
        return jnp.stack([hi, lo])

    if mesh is None:
        return jax.jit(fn)
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.jit(fn, out_shardings=NamedSharding(mesh, P()))


def _build_batched(expr: tuple, reduce: str):
    return jax.jit(jax.vmap(_make_fn(expr, reduce)))


def _build_scatter():
    """Delta-scatter: apply n (slot, word, or-mask, andnot-mask) updates
    to a resident device plane as ONE fused gather/modify/scatter.  The
    update axis leads so the program-cache bucket gauges see the
    pow2-bucketed update count (callers pad to :func:`pilosa_tpu.ops.
    bitplane.pow2_bucket` by REPEATING the last real entry — duplicate
    indices then write identical values, which XLA scatter handles
    deterministically).  No buffer donation: a concurrent reader may
    still hold the old plane, which is exactly how the fragment's
    version fence gives readers old-or-new atomicity."""

    def fn(slots, words, or_m, andnot_m, plane):
        cur = plane[slots, words]
        return plane.at[slots, words].set((cur & ~andnot_m) | or_m)

    return jax.jit(fn)


_compiled_batched = _ProgramCache(_build_batched, "plan.batched")
_compiled_total_count = _ProgramCache(_build_total_count, "plan.totalCount")
_compiled_interp = _ProgramCache(_build_interp, "interp")
_compiled_scatter = _ProgramCache(_build_scatter, "plan.scatter", maxsize=1)
# Defined before _build_anchored below (builders bind lazily at call).
_compiled_anchored = _ProgramCache(
    lambda expr, fmts: _build_anchored(expr, fmts), "plan.anchored"
)


def scatter_apply(plane, slots, words, or_m, andnot_m):
    """Dispatch one fused delta-scatter launch (update axis bucketed by
    the caller); returns the NEW plane array, old left intact."""
    # The jit cache also keys on the plane's (pow2-classed) row count;
    # track its highwater so program_cache_bounds stays an invariant.
    _note_bucket("plan.scatter.rows", int(plane.shape[0]))
    from pilosa_tpu.ops import bitplane as bp

    bp._note_shape(plane_words=int(plane.shape[1]))
    return _compiled_scatter()(slots, words, or_m, andnot_m, plane)


# ---------------------------------------------------------------------------
# anchored position-domain count (compressed-plane fast path)
# ---------------------------------------------------------------------------

def _build_anchored(expr: tuple, fmts: tuple):
    """Position-domain Count: instead of streaming dense
    (leaves x 32768)-word rows, evaluate the fold expression POINTWISE
    over the anchor leaf's sentinel-padded position vector, reading
    each leaf through its container format directly (ops/bitplane
    membership_* — dense gather / sparse searchsorted / RLE run
    search).  Sound whenever the result is a subset of the anchor
    (executor._anchor_candidates), so the count is just the number of
    anchor positions whose membership mask survives.

    ``fmts`` is the per-leaf container-format tuple — a compile static
    (it selects which membership kernel each leaf traces), which is why
    it is part of the wrapper key.  Inputs are vmapped over a leading
    slice axis: anchor uint32[S, P], payload i uint32[S, Li] or
    uint32[S, Ri, 2]; all axes pow2-bucketed by the caller so the jit
    key stays pure geometry."""
    from pilosa_tpu.ops import bitplane as bp

    def one(anchor, *payloads):
        def leaf_mask(i):
            fmt = fmts[i]
            if fmt == bp.FMT_DENSE:
                return bp.membership_dense(payloads[i], anchor)
            if fmt == bp.FMT_SPARSE:
                return bp.membership_sparse(payloads[i], anchor)
            return bp.membership_rle(payloads[i], anchor)

        def rec(e):
            if e[0] == "leaf":
                return leaf_mask(e[1])
            kids = [rec(ch) for ch in e[1:]]
            if not kids:  # empty Union
                return jnp.zeros(anchor.shape, dtype=bool)
            acc = kids[0]
            for nxt in kids[1:]:
                if e[0] == "Intersect":
                    acc = acc & nxt
                elif e[0] == "Union":
                    acc = acc | nxt
                elif e[0] == "Difference":
                    acc = acc & ~nxt
                else:  # Xor
                    acc = acc ^ nxt
            return acc

        mask = rec(expr)
        valid = anchor != jnp.uint32(bp.FMT_SENTINEL)
        return jnp.sum((mask & valid).astype(jnp.int32))

    return jax.jit(jax.vmap(one))


def compiled_anchored_count(expr: tuple, fmts: tuple) -> "_Program":
    """One jitted wrapper per (tree shape, per-leaf container-format
    tuple); compiled entries inside a wrapper key on (slice bucket,
    anchor-position bucket, per-leaf payload buckets)."""
    return _compiled_anchored(expr, fmts)


# Largest payload-entry bucket ever dispatched through an anchored
# launch (anchor vector or any leaf payload) — with the slice axis in
# _BUCKET_HIGHWATER["plan.anchored"], this derives the family's hard
# cardinality bound.  Plain dict writes: racing maxima are both valid.
_ANCHORED_HIGHWATER: dict[str, int] = {}


def anchored_count_exec(expr: tuple, fmts: tuple, anchor, payloads):
    """Dispatch one anchored count launch (slice axis leading,
    everything pow2-bucketed by the caller), recording the payload
    high-waters program_cache_bounds derives from.  Returns int32[S]
    per-slice counts."""
    hw = max(
        max((int(p.shape[1]) for p in payloads), default=1),
        int(anchor.shape[1]),
    )
    if hw > _ANCHORED_HIGHWATER.get("payload", 0):
        _ANCHORED_HIGHWATER["payload"] = hw
    if len(fmts) > _ANCHORED_HIGHWATER.get("leaves", 0):
        _ANCHORED_HIGHWATER["leaves"] = len(fmts)
    return _compiled_anchored(expr, fmts)(anchor, *payloads)


# ---------------------------------------------------------------------------
# compiled-program cardinality (ROADMAP 2a: canonical keys + hard bounds)
# ---------------------------------------------------------------------------

# family -> largest bucketed leading batch axis dispatched so far.
# Plain dict writes: racing writers both store valid maxima.
_BUCKET_HIGHWATER: dict[str, int] = {}

# family -> cumulative first-call (compile-bearing) wall ms.  Plain
# dict accumulation: a lost race under-counts telemetry, nothing more.
_COMPILE_MS: dict[str, float] = {}


def _note_bucket(family: str, bucket: int) -> None:
    if bucket > _BUCKET_HIGHWATER.get(family, 0):
        _BUCKET_HIGHWATER[family] = bucket


def _note_compile_ms(family: str, ms: float) -> None:
    _COMPILE_MS[family] = _COMPILE_MS.get(family, 0.0) + ms


def note_first_call(family: str, shape: str, start: float, ms: float) -> None:
    """A program shape's first call, the one that compiles: its wall
    time accrues to the family's ``compileMs`` gauge and is recorded as
    a ``compile`` span in the trace of the request that waited on it —
    under the dispatcher's ``launch`` on a coalesced launch, else under
    the caller's current span (``topn.dispatch`` for the TopN scorer)."""
    _note_compile_ms(family, ms)
    sp = trace.current_span()
    if sp is not None:
        sp.add_child("compile", start, ms, family=family, shape=shape)


# The TopN scorer's (``bp.score_planes``) ``first_call`` hook: its
# programs compile outside ``_Program``.
note_scorer_first_call = functools.partial(note_first_call, "topn.score")
# The same hook for the leaf-batch gather (``bp.gather_planes`` and the
# in-place writes of its outputs), under the miss's ``plan.leaves`` /
# ``plan.transfer``.
note_gather_first_call = functools.partial(note_first_call, "plan.gather")
# And for the in-place BSI aggregate (``bp.aggregate_planes``), under the
# aggregate's ``bsi.dispatch``.
note_agg_first_call = functools.partial(note_first_call, "bsi.agg")


def program_cache_compile_ms() -> dict[str, float]:
    """Cumulative compile-bearing first-call wall ms per jit family —
    the ``exec.programCache.compileMs[cache:*]`` gauges on /metrics and
    the ``compile_ms`` column of bench artifacts' perf block."""
    return {k: round(v, 3) for k, v in _COMPILE_MS.items()}


def _jit_cache_size(fn) -> int:
    """Entry count of one jax.jit wrapper's compile cache."""
    return int(fn._cache_size())


def program_cache_stats() -> dict[str, int]:
    """COMPILED-program counts per jit family — the
    ``exec.programCache.entries`` gauge on /metrics.  ``plan.*`` sums
    the compiled entries inside every live (tree shape, reduce)/(tree
    shape, mesh) wrapper (one entry per batch-shape bucket);
    ``bitplane.*`` counts compiled entries inside the module-level jit
    wrappers (the TopN scorer keys on per-fragment plane shapes).
    Every counted key is canonicalized — slice axes, plane rows,
    candidate slots, and fragment-group sizes all bucket to powers of
    two — so each family is hard-bounded by its bucket grid
    (:func:`program_cache_bounds`), not by schema churn."""
    from pilosa_tpu.ops import bitplane as bp

    out = {
        "plan.batched": sum(
            _jit_cache_size(p.fn) for p in _compiled_batched.programs()
        ),
        "plan.totalCount": sum(
            _jit_cache_size(p.fn) for p in _compiled_total_count.programs()
        ),
        "interp": sum(
            _jit_cache_size(p.fn) for p in _compiled_interp.programs()
        ),
        "plan.scatter": sum(
            _jit_cache_size(p.fn) for p in _compiled_scatter.programs()
        ),
        "plan.anchored": sum(
            _jit_cache_size(p.fn) for p in _compiled_anchored.programs()
        ),
        "bitplane.expand": (
            _jit_cache_size(bp._expand_sparse_xla)
            + _jit_cache_size(bp._expand_rle_xla)
        ),
        "bitplane.scorePlanes": sum(
            _jit_cache_size(fn) for fn in bp.SCORE_PROGRAMS
        ),
        "bitplane.gatherPlanes": sum(
            _jit_cache_size(fn) for fn in bp.GATHER_PROGRAMS
        ),
        "bitplane.scoreRows": _jit_cache_size(bp._score_rows_xla),
        "bitplane.aggregatePlanes": _jit_cache_size(bp._aggregate_planes_xla),
        "bitplane.fusedCount": _jit_cache_size(bp._fused_count_xla),
        "bitplane.topCounts": _jit_cache_size(bp._top_counts_xla),
    }
    out["total"] = sum(out.values())
    return out


def _scatter_floor() -> int:
    # Lazy: ingest.scatter imports this module inside apply().
    from pilosa_tpu.ingest import scatter as ingest_scatter

    return ingest_scatter.UPDATE_BUCKET_FLOOR


def program_cache_bounds() -> dict[str, int]:
    """Hard per-family cardinality bounds implied by the pow2 bucket
    grids at the LARGEST shapes observed so far (``exec.programCache.
    bound`` on /metrics).  ``entries <= bound`` is an invariant: a
    family exceeding its bound means some caller stopped canonicalizing
    its compile key — exactly what the churny-schema regression test
    asserts.  Families whose keys carry arbitrary caller shapes
    (``bitplane.fusedCount``) have no derivable bound and are omitted."""
    from pilosa_tpu.ops import bitplane as bp

    hw = bp.shape_highwater()
    rb = bp.ROW_BLOCK
    # A program over planes that live on their slice's home device
    # compiles one executable a device.
    n_dev = bp.mesh_device_count()

    def slice_classes(family: str) -> int:
        return bp.bucket_classes(max(_BUCKET_HIGHWATER.get(family, 1), 1))

    return {
        # distinct wrappers x slice-bucket classes per wrapper
        "plan.batched": (
            _compiled_batched.cache_info().currsize
            * slice_classes("plan.batched")
        ),
        "plan.totalCount": (
            _compiled_total_count.cache_info().currsize
            * slice_classes("plan.totalCount")
        ),
        # reduce-kind wrappers x slice x leaf x op-table x out classes —
        # pure geometry: the bound does NOT grow with how many distinct
        # expression mixes ever fused, which is the whole point.
        "interp": (
            _compiled_interp.cache_info().currsize
            * slice_classes("interp")
            * bp.bucket_classes(max(_INTERP_HIGHWATER.get("leaves", 1), 1))
            * bp.bucket_classes(
                max(_INTERP_HIGHWATER.get("ops", FUSE_OPS_FLOOR), FUSE_OPS_FLOOR),
                FUSE_OPS_FLOOR,
            )
            * bp.bucket_classes(max(_INTERP_HIGHWATER.get("outs", 1), 1))
        ),
        # one wrapper x update-count bucket classes (floor
        # ingest.scatter.UPDATE_BUCKET_FLOOR) x plane-row shape classes
        # (planes pad rows to pow2, floor ROW_BLOCK; the word axis is
        # uniform, so it contributes no classes) — on each device
        "plan.scatter": (
            _compiled_scatter.cache_info().currsize
            * n_dev
            * bp.word_classes()
            * bp.bucket_classes(
                max(_BUCKET_HIGHWATER.get("plan.scatter", _scatter_floor()),
                    _scatter_floor()),
                _scatter_floor(),
            )
            * bp.bucket_classes(
                max(_BUCKET_HIGHWATER.get("plan.scatter.rows", rb), rb), rb
            )
        ),
        # (self-src + host-src) x fragment-group classes (at most
        # log2(bp.SCORE_GROUP) + 1 whatever the slice count: larger
        # groups relaunch the SCORE_GROUP program) x plane-row classes
        # x candidate-slot classes — on each device
        # ... x the planes' row-width classes (bp.row_words: a plane
        # is as wide as its columns ask for), as every family below
        # whose operands are plane mirrors
        "bitplane.scorePlanes": (
            2
            * n_dev
            * bp.word_classes()
            * bp.bucket_classes(max(hw.get("score_frags", 1), 1))
            * bp.bucket_classes(max(hw.get("score_rows", rb), rb), rb)
            * bp.bucket_classes(max(hw.get("score_slots", rb), rb), rb)
        ),
        # the walked scorer: plane-row classes x row-width classes, on
        # each device; the text's numbers are operands
        "bitplane.scoreRows": (
            n_dev
            * bp.word_classes()
            * bp.bucket_classes(max(hw.get("walk_rows", rb), rb), rb)
        ),
        # the gather: member classes (as the scorer's) x plane-row
        # classes x leaves of a run; its in-place writes: one a (block
        # = slice-bucket class x leaves, launch shape), and the
        # constant column's one a block — all on each device
        "bitplane.gatherPlanes": (
            n_dev
            * bp.word_classes()
            * (
                bp.bucket_classes(max(hw.get("gather_frags", 1), 1))
                * bp.bucket_classes(max(hw.get("gather_rows", rb), rb), rb)
                * max(hw.get("gather_leaves", 1), 1)
                + bp.bucket_classes(max(hw.get("place_rows", 1), 1))
                * max(hw.get("place_leaves", 1), 1)
                * (
                    bp.bucket_classes(max(hw.get("gather_frags", 1), 1))
                    * max(hw.get("place_leaves", 1), 1)
                    + 1
                )
            )
        ),
        # the in-place aggregate: the (expression, leaf layout, unit
        # kinds) called so far — what a call's text decides, as the
        # wrappers of plan.batched — x member classes (at most
        # log2(bp.AGG_GROUP) + 1) x plane-row classes a unit (the
        # mirrors are operands: a shape each) — on each device.  What a
        # fragment holds, and where, is data and makes no program.
        "bitplane.aggregatePlanes": (
            len(bp._AGG_SEEN)
            * n_dev
            * bp.bucket_classes(max(hw.get("agg_frags", 1), 1))
            * (
                bp.word_classes()
                * bp.bucket_classes(max(hw.get("agg_rows", rb), rb), rb)
            )
            ** max(hw.get("agg_units", 1), 1)
        ),
        "bitplane.topCounts": n_dev * bp.word_classes() * bp.bucket_classes(
            max(hw.get("top_rows", rb), rb), rb
        ),
        # (tree shape x container-format tuple) wrappers x slice-bucket
        # classes x payload-length bucket classes raised to the leaf
        # count — the container-length bucketing rule: every anchor /
        # payload axis pads to payload_bucket (floor
        # PAYLOAD_BUCKET_FLOOR), so per-leaf length variation compiles
        # at most one entry per bucket class, and format variation
        # lands in DISTINCT wrappers (counted by currsize), never in
        # unbounded jit keys.
        "plan.anchored": (
            _compiled_anchored.cache_info().currsize
            * slice_classes("plan.anchored")
            * bp.bucket_classes(
                max(
                    _ANCHORED_HIGHWATER.get(
                        "payload", bp.PAYLOAD_BUCKET_FLOOR
                    ),
                    bp.PAYLOAD_BUCKET_FLOOR,
                ),
                bp.PAYLOAD_BUCKET_FLOOR,
            )
            # +1: the anchor-position axis keys alongside the per-leaf
            # payload axes.
            ** (max(_ANCHORED_HIGHWATER.get("leaves", 1), 1) + 1)
        ),
        # (sparse + rle) expansion wrappers x payload bucket classes,
        # on each device
        "bitplane.expand": 2 * n_dev * bp.bucket_classes(
            max(
                hw.get("expand_payload", bp.PAYLOAD_BUCKET_FLOOR),
                bp.PAYLOAD_BUCKET_FLOOR,
            ),
            bp.PAYLOAD_BUCKET_FLOOR,
        ),
    }


def program_cache_entries() -> int:
    """Total compiled-program cache entries (the headline gauge)."""
    return program_cache_stats()["total"]


def clear_program_caches() -> None:
    """Drop every compiled program and the bucket high-water marks —
    test isolation for the cardinality regression suite (a process
    that already ran queries would otherwise leak entries into another
    test's gauge assertions)."""
    from pilosa_tpu.ops import bitplane as bp

    _compiled_batched.cache_clear()
    _compiled_total_count.cache_clear()
    _compiled_interp.cache_clear()
    _compiled_scatter.cache_clear()
    _compiled_anchored.cache_clear()
    _BUCKET_HIGHWATER.clear()
    _INTERP_HIGHWATER.clear()
    _ANCHORED_HIGHWATER.clear()
    _COMPILE_MS.clear()
    bp._SHAPE_HIGHWATER.clear()
    bp._SCORE_SEEN.clear()
    bp._AGG_SEEN.clear()
    for fn in bp.GATHER_PROGRAMS + bp.SCORE_PROGRAMS + (
        bp._aggregate_planes_xla,
        bp._score_rows_xla,
        bp._fused_count_xla,
        bp._top_counts_xla,
        bp._expand_sparse_xla,
        bp._expand_rle_xla,
    ):
        fn.clear_cache()
