"""The BSI ripple — comparison, Sum, and Min/Max over bit-planes.

One implementation, two array backends: the fused device kernels
(exec/plan.py embeds these into jitted XLA programs, ``xp=jax.numpy``)
and the host reference path (plan.eval_expr_np, ``xp=numpy``) share
these functions verbatim, so the device programs can never drift from
the host semantics.

Everything here is an and/andnot/or cascade over limb planes plus
popcount reductions — exactly the op mix ``ops/bitplane.py`` already
executes as one fused bitwise+popcount pass.  Predicates arrive as
DATA (a packed :func:`pilosa_tpu.bsi.pred_row`), so a compiled program
serves every predicate value of its (op kind, depth bucket).
"""

from __future__ import annotations

_FULL = 0xFFFFFFFF


def _bit_mask(word, xp):
    """uint32 word (0/1), or a vector of them -> all-ones/all-zeros
    uint32 masks, without overflow-warning-prone unsigned negation."""
    return (word & xp.uint32(1)) * xp.uint32(_FULL)


def magnitude_cmp(exists, planes, pred_bits, xp):
    """Range-encoded ripple: partition the ``exists`` columns into
    (lt, eq, gt) against the unsigned magnitude whose bit ``k`` is
    ``pred_bits[k] & 1``.  High plane to low: columns still equal on
    every higher bit split on the current one."""
    eq = exists
    lt = xp.zeros_like(exists)
    gt = xp.zeros_like(exists)
    # every plane's mask and its complement in two vector operations:
    # made a word at a time they are a handful of scalar device
    # operations a plane, in every launch of every program
    masks = _bit_mask(pred_bits, xp)
    nmasks = ~masks
    for k in reversed(range(len(planes))):
        b = planes[k]
        lt = lt | (eq & ~b & masks[k])
        gt = gt | (eq & b & nmasks[k])
        eq = eq & (b ^ nmasks[k])
    return lt, eq, gt


def signed_cmp(op, exists, sign, planes, pred, xp):
    """One signed comparison row.  ``pred`` is a packed predicate row
    (bit ``k`` of the magnitude at word ``k``, sign flag at word
    ``len(planes)``); ``op`` is a static tag (lt/le/eq/ne/ge/gt).

    Sign-magnitude composition: the magnitude partition applies to the
    matching sign group, with ordering inverted among negatives; the
    predicate's own sign selects between the two composition cases via
    a data mask, so positive and negative predicates share one
    compiled program."""
    depth = len(planes)
    lt, eq, gt = magnitude_cmp(exists, planes, pred[:depth], xp)
    nm = _bit_mask(pred[depth], xp)  # all-ones iff the predicate is negative
    pos = exists & ~sign
    neg = exists & sign

    eq_row = (~nm & pos & eq) | (nm & neg & eq)
    if op == "eq":
        return eq_row
    if op == "ne":
        return exists & ~eq_row
    lt_row = (~nm & (neg | (pos & lt))) | (nm & neg & gt)
    if op == "lt":
        return lt_row
    if op == "le":
        return lt_row | eq_row
    gt_row = (~nm & pos & gt) | (nm & (pos | (neg & lt)))
    if op == "gt":
        return gt_row
    if op == "ge":
        return gt_row | eq_row
    raise ValueError(f"unknown BSI comparison op {op!r}")


def between_row(exists, sign, planes, pred_lo, pred_hi, xp):
    """``lo <= v <= hi`` as two fused ripples sharing the plane reads."""
    return signed_cmp("ge", exists, sign, planes, pred_lo, xp) & signed_cmp(
        "le", exists, sign, planes, pred_hi, xp
    )


def sum_vec(exists, sign, planes, filt, xp, popcount):
    """Per-slice Sum partials: int vector
    ``[pos_0..pos_{D-1}, neg_0..neg_{D-1}, n]`` where ``pos_k`` /
    ``neg_k`` count set bits of plane ``k`` among non-negative /
    negative valued columns and ``n`` counts valued columns — the
    popcount-weighted plane dot finishes on the host in unbounded
    Python ints: ``sum = Σ 2^k (pos_k - neg_k)``.  Each partial covers
    one slice-row (<= 2^20 bits), so int32 is exact."""
    base = exists if filt is None else exists & filt
    pos = base & ~sign
    neg = base & sign
    parts = [popcount(p & pos) for p in planes]
    parts += [popcount(p & neg) for p in planes]
    parts.append(popcount(base))
    return xp.stack(parts)


def minmax_vec(which, exists, sign, planes, filt, xp, popcount, where):
    """Per-slice Min/Max partials via greedy plane descent: int vector
    ``[bit_0..bit_{D-1}, negative, count]`` — the chosen magnitude
    bits, whether the extreme is negative, and how many columns hold
    it (count 0 = no valued columns in the slice).

    Min prefers the negative group (where the LARGEST magnitude wins);
    Max prefers the non-negative group (largest magnitude wins too) —
    so both run ONE descent whose direction is maximize-within-group,
    falling back to the opposite group with a minimizing descent.  The
    group choice and both descents are data-dependent selects inside
    the fused program, never separate compiles."""
    base = exists if filt is None else exists & filt
    pos = base & ~sign
    neg = base & sign
    if which == "min":
        prefer, other = neg, pos
    else:
        prefer, other = pos, neg
    use_prefer = xp.asarray(popcount(prefer) > 0)
    cand = where(use_prefer, prefer, other)
    # maximize magnitude within the preferred group, minimize in the
    # fallback group (see docstring) — identical rule for min and max.
    maximize = use_prefer

    bits = [None] * len(planes)
    for k in reversed(range(len(planes))):
        b = planes[k]
        with_one = cand & b
        n1 = popcount(with_one)
        ntot = popcount(cand)
        # maximize: take bit 1 iff any candidate has it;
        # minimize: take bit 1 only when every candidate has it.
        choose1 = where(maximize, xp.asarray(n1 > 0), xp.asarray(n1 == ntot))
        cand = where(choose1, with_one, cand & ~b)
        bits[k] = xp.asarray(choose1).astype(xp.int32)
    negative = (
        use_prefer if which == "min" else xp.logical_not(use_prefer)
    ).astype(xp.int32)
    return xp.stack(bits + [negative, xp.asarray(popcount(cand), dtype=xp.int32)])


# ---------------------------------------------------------------------------
# ripple as interpreter ops (exec/plan.py fused multi-query programs)
# ---------------------------------------------------------------------------
#
# The third backend: instead of an array module, ``em`` is an opcode
# emitter (plan.FuseEmitter) and every ``xp`` operation becomes one
# packed int32 instruction row.  The emitted stream reproduces the
# array functions above operation for operation — same OR-accumulation
# order, same andnot/xor factoring — so a fused interpreter launch is
# byte-identical to the direct compiled ripple.  Value numbering inside
# the emitter shares subterms (pos/neg/ripple state) across the two
# ripples of a ``between`` and across queries lowered into one table.


def lower_magnitude_cmp(em, exists, planes, pred):
    """Emit :func:`magnitude_cmp` as interpreter ops; ``exists`` /
    ``planes[k]`` / ``pred`` are register ids, the return is the
    ``(lt, eq, gt)`` register triple.  ``m_k`` comes from the MASKW op
    (broadcast of predicate word ``k``), so the predicate stays DATA —
    one lowered stream serves every constant of its depth bucket."""
    eq = exists
    lt = gt = None
    for k in reversed(range(len(planes))):
        b = planes[k]
        m = em.maskw(pred, k)
        lt_term = em.and_(em.andnot(eq, b), m)
        lt = lt_term if lt is None else em.or_(lt, lt_term)
        gt_term = em.andnot(em.and_(eq, b), m)
        gt = gt_term if gt is None else em.or_(gt, gt_term)
        # eq & (b ^ ~m)  ==  eq & ~(b ^ m)
        eq = em.andnot(eq, em.xor(b, m))
    # BSI depths bucket to multiples of 8 (bsi.pad_depth), so planes is
    # never empty and lt/gt are always materialized.
    return lt, eq, gt


def lower_signed_cmp(em, op, exists, sign, planes, pred):
    """Emit :func:`signed_cmp` as interpreter ops; returns the result
    row's register id.  Same sign-magnitude composition, with the
    predicate's sign mask (word ``depth``) selecting between the
    positive- and negative-predicate cases as data."""
    depth = len(planes)
    lt, eq, gt = lower_magnitude_cmp(em, exists, planes, pred)
    nm = em.maskw(pred, depth)
    pos = em.andnot(exists, sign)
    neg = em.and_(exists, sign)

    eq_row = em.or_(
        em.andnot(em.and_(pos, eq), nm), em.and_(em.and_(neg, eq), nm)
    )
    if op == "eq":
        return eq_row
    if op == "ne":
        return em.andnot(exists, eq_row)
    lt_row = em.or_(
        em.andnot(em.or_(neg, em.and_(pos, lt)), nm),
        em.and_(em.and_(neg, gt), nm),
    )
    if op == "lt":
        return lt_row
    if op == "le":
        return em.or_(lt_row, eq_row)
    gt_row = em.or_(
        em.andnot(em.and_(pos, gt), nm),
        em.and_(em.or_(pos, em.and_(neg, lt)), nm),
    )
    if op == "gt":
        return gt_row
    if op == "ge":
        return em.or_(gt_row, eq_row)
    raise ValueError(f"unknown BSI comparison op {op!r}")


def lower_between(em, exists, sign, planes, pred_lo, pred_hi):
    """``lo <= v <= hi`` as two lowered ripples; the emitter's value
    numbering shares the pos/neg sign-group rows between them."""
    return em.and_(
        lower_signed_cmp(em, "ge", exists, sign, planes, pred_lo),
        lower_signed_cmp(em, "le", exists, sign, planes, pred_hi),
    )


def decode_minmax(vec, depth: int) -> tuple[int, int] | None:
    """One slice's ``minmax_vec`` output -> ``(value, count)`` in
    Python ints, or None when the slice holds no valued column."""
    count = int(vec[depth + 1])
    if count <= 0:
        return None
    mag = 0
    for k in range(depth):
        if int(vec[k]):
            mag |= 1 << k
    return (-mag if int(vec[depth]) else mag), count


def decode_sum(vec, depth: int) -> tuple[int, int]:
    """One slice's ``sum_vec`` output -> ``(sum, count)`` in Python
    ints (exact at any depth — the weights never touch device
    arithmetic)."""
    total = 0
    for k in range(depth):
        total += (1 << k) * (int(vec[k]) - int(vec[depth + k]))
    return total, int(vec[2 * depth])
