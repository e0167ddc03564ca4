"""A folded TopN's parts as a struct of arrays.

A prep entry of the folded TopN (``Executor._topn_folded_build``) has a
part a fragment: what the scorer reads there and what the selection
needs to turn the fetched scores into winners.  At a deployment's size
that is ~1,000 parts of ~64 candidates, and a walk over them in Python
costs a few numpy calls of microseconds a part — each of which drops
the GIL and has to win it back from every other request thread.  So the
entry stacks, once a build, what those walks read:

* ``ScoreStack`` — the scorer's operands by program shape (the planes
  of a group as a tuple, its slot vectors as one ``int32[members,
  slots]``, its src slots as one ``int32[members]``) and where each
  part's score row lies in the one flat vector the fetch gives back;
* ``TopStack`` — every part's candidates padded to one ``[parts,
  width]`` shape: each candidate's position in the sorted union, the
  counts no scoring changes (a short-circuited part's final counts, the
  sparse tier's probed counts), where a count is a fetched score and
  which one, which candidates are a part's own (phase 1 ranks those
  only), and the thresholds a row keeps by.

Neither depends on the text where every part's src is a row of its own
plane: a direct build's stacks are kept across texts
(``Executor._topn_kept_text``), and a text looks its src row's slots up
in a ``SrcTable`` (``src_slots``) and takes the ``ScoreStack`` with
them (``for_src_row``).

``select`` is both protocol phases over those arrays: no call a part.
Whether a part came from its fragment's kept layout, was walked, is
tanimoto-filtered or short-circuited is in the arrays (masks, lengths,
thresholds), so one selection serves every folded entry.  The arrays of
an entry are shared by every query that uses it and are never written;
the fetched score vector is the only state an answer owns.
"""

from __future__ import annotations

from dataclasses import dataclass, replace

import numpy as np

from pilosa_tpu.obs import perf as perf_mod

NO_SCORES = np.empty(0, np.int32)  # of an entry with nothing to score
_KEY_PAD = np.iinfo(np.int64).max


@dataclass(frozen=True, eq=False)
class ScoreGroup:
    """The members one scorer program shape serves, in launch order."""

    planes: tuple  # a device mirror snapshot a member
    slots: np.ndarray  # int32[members, slots], C-contiguous
    src_slots: np.ndarray | None  # int32[members]: the src's slot in the plane
    srcs: tuple | None  # else a host-snapshot src row a member


@dataclass(frozen=True, eq=False)
class ScoreStack:
    """Everything ``Executor._score_topn_parts`` reads, grouped as the
    scorer is launched.  ``base[i]`` is where entry ``i``'s score row
    starts in the flat score vector (-1: nothing of it is scored on the
    device), as long as the entry's slot vector; ``live`` are the
    scored entries themselves, group after group, which the host
    fallback walks, and ``live_base`` their bases.  ``members[g]`` are
    the indices of group ``g``'s entries among the entries the stack was
    made from.  ``src_row``: the row every entry without src words
    reads its src from, where that is not the row its TopState names (a
    stack that serves another text than the one its states were made
    for, ``for_src_row``); None: each state's own."""

    groups: tuple
    live: tuple
    live_base: np.ndarray
    base: np.ndarray
    size: int
    rows: int
    n_bytes: int
    members: tuple = ()
    src_row: int | None = None

    def hand_out(self, entries, scores: np.ndarray) -> None:
        """Give each entry's TopState its row of ``scores`` (the
        unfolded path's per-fragment ``top_score_arrays`` reads it)."""
        for entry, b in zip(entries, self.base.tolist()):
            if b >= 0:
                entry[0].counts = scores[b : b + len(entry[1].slots)]


def score_stack(entries) -> ScoreStack:
    """Group score entries ``(TopState, SubRef, src_words, src_slot,
    fragment)`` by program shape (sub shape, plane rows, home device)
    and by where their src is read from, and stack each group's
    operands.  An entry without a SubRef has nothing to score."""
    keyed: dict[tuple, list[int]] = {}
    for i, entry in enumerate(entries):
        ref = entry[1]
        if ref is not None:
            keyed.setdefault(
                (ref.shape, ref.plane_rows, ref.device, entry[3] is None), []
            ).append(i)
    base = np.full(len(entries), -1, np.int64)
    groups, live, order, in_group = [], [], [], []
    size = rows = n_bytes = 0
    for (shape, plane_rows, _dev, host_src), idx in keyed.items():
        members = [entries[i] for i in idx]
        n, w = len(members), int(shape[0])
        base[idx] = size + w * np.arange(n)
        size += n * w
        # Scorer roofline accounting: each member's fused scoring pass
        # streams its whole plane snapshot (a launch's pad repeats are
        # bucketing, not counted).
        rows += n * int(plane_rows)
        n_bytes += n * perf_mod.plane_bytes(int(plane_rows), int(shape[1]))
        groups.append(
            ScoreGroup(
                planes=tuple(m[1].plane for m in members),
                slots=np.concatenate([m[1].slots for m in members])
                .astype(np.int32, copy=False)
                .reshape(n, w),
                # Same-plane src slot for every member -> zero src bytes
                # cross the host boundary (and no extra leaf shapes in
                # the jit key); otherwise one stacked host-snapshot
                # transfer per launch.
                src_slots=(
                    None
                    if host_src
                    else np.asarray([m[3] for m in members], dtype=np.int32)
                ),
                # a host-snapshot src is a full-width row; the scorer
                # reads as many words of it as the members' planes have
                srcs=(
                    tuple(m[2][: int(shape[1])] for m in members)
                    if host_src
                    else None
                ),
            )
        )
        live.extend(members)
        order.extend(idx)
        in_group.append(np.asarray(idx, np.intp))
    return ScoreStack(
        tuple(groups),
        tuple(live),
        base[order],
        base,
        size,
        rows,
        n_bytes,
        tuple(in_group),
    )


@dataclass(frozen=True, eq=False)
class SrcTable:
    """Where every dense-tier row of a stack's parts lies in each part's
    plane, as one listing sorted by row id: ``slot[k]`` is the slot of
    row ``ids[k]`` in the plane of part ``part[k]``.  A row is held once
    a part at most, so it is held by every part where it is listed
    ``parts`` times."""

    ids: np.ndarray  # int64, ascending
    part: np.ndarray  # intp
    slot: np.ndarray  # int32
    parts: int


def src_table(tiers) -> SrcTable:
    """The ``SrcTable`` of ``tiers``, each part's dense tier as ``(row
    ids ascending, their slots)``."""
    lens = np.fromiter((len(t[0]) for t in tiers), np.int64, len(tiers))
    ids = np.concatenate([t[0] for t in tiers])
    order = np.argsort(ids, kind="stable")
    return SrcTable(
        ids[order],
        np.repeat(np.arange(len(tiers)), lens)[order],
        np.concatenate([t[1] for t in tiers]).astype(np.int32)[order],
        len(tiers),
    )


def src_slots(table: SrcTable, row: int) -> np.ndarray | None:
    """``int32[parts]``: row ``row``'s slot in each part's plane, in a
    fixed number of numpy calls; None where some part does not hold it
    in its dense tier."""
    lo = int(np.searchsorted(table.ids, row))
    hi = lo + table.parts
    # listed once a part at most: ``parts`` listings from the first are
    # all of the row's where the last of them is the row's
    if hi > len(table.ids) or table.ids[hi - 1] != row:
        return None
    out = np.empty(table.parts, np.int32)
    out[table.part[lo:hi]] = table.slot[lo:hi]
    return out


def for_src_row(stack: ScoreStack, slots: np.ndarray, row: int) -> ScoreStack:
    """``stack`` for the src row ``row`` of the parts' own planes: each
    group's src slots taken from ``slots`` (a part's slot, in the order
    of the entries ``stack`` was made from), and the host fallback told
    to read that row.  Planes, candidate slots and bases are shared."""
    return replace(
        stack,
        groups=tuple(
            replace(g, src_slots=slots[m])
            for g, m in zip(stack.groups, stack.members)
        ),
        src_row=row,
    )


def flatten_scores(stack: ScoreStack, launches) -> np.ndarray:
    """The fetched launches of every group as the one flat vector
    ``ScoreStack.base`` indexes.  ``launches``: a list of host arrays
    ``int32[bucket, slots]`` a group, the last padded by repeats that
    are dropped here."""
    pieces = []
    for group, outs in zip(stack.groups, launches):
        left = len(group.planes)
        for out in outs:
            pieces.append(out[:left].reshape(-1))
            left -= len(out)
    return np.concatenate(pieces) if pieces else NO_SCORES


def host_scores(stack: ScoreStack, counts) -> np.ndarray:
    """The flat score vector from the host fallback's count vectors, one
    a ``stack.live`` entry (each as long as its dense candidates)."""
    flat = np.zeros(stack.size, np.int32)
    for b, c in zip(stack.live_base.tolist(), counts):
        flat[b : b + len(c)] = c
    return flat


@dataclass(frozen=True, eq=False)
class TopStack:
    """A folded entry's parts for ``select`` (see the module's text).
    Row ``p`` is part ``p``; a row shorter than ``width`` is padded with
    count 0, which no threshold keeps."""

    union: np.ndarray  # int64[U], sorted: every id any part lists
    upos: np.ndarray  # intp[P, W]: a candidate's position in union
    fixed: np.ndarray  # int64[P, W]: final counts and sparse-tier counts
    dense: np.ndarray | None  # bool[P, W]: the count is a fetched score ...
    gidx: np.ndarray | None  # intp[P, W]: ... this one of the flat vector
    own: np.ndarray | None  # bool[P, W]; None: every candidate is own
    min_threshold: np.ndarray  # int64[P, 1]
    tanimoto: np.ndarray | None  # int64[P, 1]; None: no row has one
    src_count: np.ndarray | None  # int64[P, 1]
    cached: np.ndarray | None  # int64[P, W] cached counts (tanimoto)


def _flat_index(rows: np.ndarray, lens: np.ndarray, width: int) -> np.ndarray:
    """``[r * width + j for r, n in zip(rows, lens) for j in range(n)]``."""
    starts = np.cumsum(lens) - lens
    return np.repeat(rows * width - starts, lens) + np.arange(int(lens.sum()))


def _positions(union: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """Where each of ``ids`` (all members of the sorted ``union``)
    stands in it.  Row ids are small numbers as a rule: then a table
    over the id range answers by one take what a binary search an id
    (cache-missing, ~40 ns each) would."""
    top = int(union[-1])
    if union[0] >= 0 and top < 16 * len(ids) + 1024:
        table = np.zeros(top + 1, np.intp)
        table[union] = np.arange(len(union))
        return table[ids]
    return np.searchsorted(union, ids)


def _lens(arrays) -> np.ndarray:
    return np.fromiter(map(len, arrays), np.int64, len(arrays))


def stack_parts(parts, union: np.ndarray, score: ScoreStack) -> TopStack:
    """Stack a folded entry's ``parts`` — ``(fragment, cand_ids,
    own_mask, TopState, SubRef, src_words, src_slot)``, ``own_mask``
    over the TopState's listing or None where all of it is the part's
    own — against ``score``, the ``score_stack`` of the same parts in
    the same order.  A part whose TopState is final (``done_ids``: no
    src, or nothing to intersect here) is a row of final counts: those
    are positive, the candidate filter dropped the rest, so the same
    ``count > 0`` keeps them that keeps a scored count."""
    sts = [p[3] for p in parts]
    n_parts = len(parts)
    is_done = np.fromiter(
        (st.done_ids is not None for st in sts), bool, n_parts
    )
    ids = [st.cand_ids if st.done_ids is None else st.done_ids for st in sts]
    lens = _lens(ids)
    width = max(int(lens.max()), 1)
    rows = np.arange(n_parts)

    every = (
        slice(None) if (lens == width).all() else _flat_index(rows, lens, width)
    )

    def spread(values, dtype, at=every):
        out = np.zeros(n_parts * width, dtype)
        out[at] = values
        return out.reshape(n_parts, width)

    upos = spread(_positions(union, np.concatenate(ids)), np.intp)

    fixed = np.zeros(n_parts * width, np.int64)
    scored = np.flatnonzero(~is_done)
    if len(scored) < n_parts:
        done = np.flatnonzero(is_done)
        fixed[_flat_index(done, lens[done], width)] = np.concatenate(
            [sts[i].done_cnts for i in done]
        )
    sparse = [sts[i].sparse_pos for i in scored]
    if any(map(len, sparse)):
        at = np.repeat(scored * width, _lens(sparse)) + np.concatenate(sparse)
        fixed[at] = np.concatenate([sts[i].sparse_cnt for i in scored])
    fixed = fixed.reshape(n_parts, width)

    dense = gidx = None
    if score.size:
        # Score k of a part is the count of its k-th dense candidate.
        on_dev = scored[score.base[scored] >= 0]
        pos = [sts[i].dense_pos for i in on_dev]
        n_pos = _lens(pos)
        at = np.repeat(on_dev * width, n_pos) + np.concatenate(pos)
        dense = spread(True, bool, at)
        starts = np.cumsum(n_pos) - n_pos
        gidx = spread(
            np.repeat(score.base[on_dev] - starts, n_pos)
            + np.arange(int(n_pos.sum())),
            np.intp,
            at,
        )

    own = None
    masks = [p[2] for p in parts]
    if any(m is not None for m in masks):
        own = spread(
            np.concatenate(
                [np.ones(n, bool) if m is None else m for m, n in zip(masks, lens)]
            ),
            bool,
        )

    def column(attr):
        return np.fromiter(
            (getattr(st, attr) for st in sts), np.int64, n_parts
        ).reshape(n_parts, 1)

    tanimoto = src_count = cached = None
    if any(st.tanimoto > 0 for st in sts):
        tanimoto, src_count = column("tanimoto"), column("src_count")
        cached = spread(
            np.concatenate(
                [st.done_cnts if st.cand_cached is None else st.cand_cached for st in sts]
            ),
            np.int64,
        )
    stack = TopStack(
        union,
        upos,
        fixed,
        dense,
        gidx,
        own,
        column("min_threshold"),  # 0 on a final part, as it was made
        tanimoto,
        src_count,
        cached,
    )
    # Shared by every query of the entry, concurrently: a write raises.
    for arr in vars(stack).values():
        if arr is not None:
            arr.flags.writeable = False
    return stack


def select(st: TopStack, scores: np.ndarray, n: int):
    """Both phases of the TopN protocol over a folded entry: each
    part's ``n`` best own candidates by (count falling, id rising) among
    those its thresholds keep, the union of those winners, and for each
    winner the sum over ALL parts of its kept counts (reference reduce:
    Pairs.Add, cache.go:312-334), best first and trimmed to ``n``
    (0: all).  ``scores``: the flat fetched score vector of the entry's
    ``ScoreStack``.  Returns ``(ids, sums)``.

    The arithmetic is ``Fragment.top_score_arrays`` +
    ``Fragment.select_winners`` a part and ``merge_counts_by_id`` over
    them (``tests/test_topn_select.py`` holds it to that composition),
    in a fixed number of numpy calls whatever the number of parts."""
    cnts = st.fixed
    if st.dense is not None:
        cnts = np.where(st.dense, scores[st.gidx], cnts)
    keep = (cnts > 0) & (cnts >= st.min_threshold)
    if st.tanimoto is not None:
        denom = st.cached + st.src_count - cnts
        with np.errstate(divide="ignore", invalid="ignore"):
            score = np.ceil(cnts * 100.0 / denom)
        keep = np.where(
            st.tanimoto > 0, (cnts > 0) & (score > st.tanimoto), keep
        )
    # Phase 1.  A part trims only where it keeps more than n of its own:
    # there one composite key a row (count falling, then position in the
    # sorted union = id rising; unique within a row) and its n-th
    # smallest value.  No sort where n covers every row.
    win = keep if st.own is None else keep & st.own
    if n:
        over = np.flatnonzero(np.count_nonzero(win, axis=1) > n)
        if len(over):
            key = np.where(
                win[over], st.upos[over] - cnts[over] * len(st.union), _KEY_PAD
            )
            nth = np.partition(key, n - 1, axis=1)[:, n - 1 : n]
            win = win.copy()
            win[over] = key <= nth
    winners = np.zeros(len(st.union), bool)
    winners[st.upos[win]] = True
    # Phase 2: exact sums for the winners, from every part that keeps
    # them (a float sum of integers is exact under 2^53).
    sums = np.bincount(
        st.upos.reshape(-1),
        weights=np.where(keep, cnts, 0).reshape(-1),
        minlength=len(st.union),
    ).astype(np.int64)
    ids, sums = st.union[winners], sums[winners]
    order = np.lexsort((ids, -sums))
    if n and n < len(order):
        order = order[:n]
    return ids[order], sums[order]
