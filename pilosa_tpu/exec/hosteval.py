"""Host (numpy) query evaluation — the degraded-mode data plane.

The host planes are AUTHORITATIVE (fragments write host-side and mirror
to HBM), and every device kernel in the system has a backend-generic
numpy formulation: the fold algebra and BSI ripple evaluate through
``plan.eval_expr_np`` (the same ``bsi/ripple.py`` code the fused XLA
programs embed), and TopN scoring is a popcount of ``row AND src`` per
candidate.  So when the accelerator is quarantined
(device/health.py), a node can keep answering BYTE-IDENTICALLY from
host memory — slower, but correct by construction.

This module is that fallback path, production-grade rather than
test-only:

* ``rows`` / ``count`` / ``agg_partials`` cover the Count/Bitmap
  algebra, Range/BSI comparisons (± predicates, between), and the BSI
  aggregates' partial vectors — op-for-op the arrays the device
  programs produce, decoded by the same executor code.
* ``score_topn_parts`` fills the folded TopN scorer's dense count
  vectors from ``Fragment._row_words_host`` rows, matching
  ``bp.score_planes`` exactly (popcount of candidate-row AND src).

Degraded throughput is admission-classed for free: the gates sit in
FRONT of the executor and their shed decision keys on the EWMA of
observed service time per class (net/admission.py), so when host
evaluation stretches service times the node sheds 429+Retry-After at
the door instead of collapsing into queue timeouts.  The
``exec.hostEval.*`` counters and the ``hosteval`` trace span make the
fallback visible per query.
"""

from __future__ import annotations

import time

import numpy as np

from pilosa_tpu.exec import plan
from pilosa_tpu.obs import perf as perf_mod
from pilosa_tpu.ops import bitplane as bp


def popcount_words(arr: np.ndarray) -> int:
    """Popcount of a uint32 word array (numpy>=2 bitwise_count, else
    unpackbits) — the host analog of the fused popcount reduce."""
    if hasattr(np, "bitwise_count"):
        return int(np.bitwise_count(arr).sum())
    return int(np.unpackbits(arr.view(np.uint8)).sum())


class HostEvaluator:
    """Evaluates bitmap call trees over an executor's authoritative
    host planes.  Stateless beyond the executor handle — safe to share
    across request threads."""

    def __init__(self, executor):
        self.ex = executor

    def _count(self, what: str, n: int = 1) -> None:
        self.ex.holder.stats.count_with_custom_tags(
            "exec.hostEval.queries", n, [f"kind:{what}"]
        )

    def _slice_rows(self, index: str, c, slices, reduce: str = "row"):
        """Per-slice evaluated result rows (uint32[words] or None) for
        an already-BSI-rewritten call tree.  The pass streams each
        slice's leaf rows once, so it records into the launch telemetry
        as a ``hosteval`` site launch (the degraded-mode row of the
        /debug/perf roofline table)."""
        expr, leaves = plan.decompose(c)
        t0 = time.monotonic()
        out = {}
        for s in slices:
            rows = [
                self.ex._leaf_row_host(index, leaf, s) for leaf in leaves
            ]
            out[s] = plan.eval_expr_np(expr, rows, bp.WORDS_PER_SLICE)
        n_rows = len(slices) * len(leaves)
        if perf_mod.enabled():
            perf_mod.record_launch(
                "hosteval",
                reduce=reduce,
                rows=n_rows,
                n_bytes=perf_mod.plane_bytes(n_rows, bp.WORDS_PER_SLICE),
                total_ms=(time.monotonic() - t0) * 1e3,
                trace_id=perf_mod.current_trace_id(),
            )
        return out

    def rows(self, index: str, c, slices: list[int]) -> dict:
        """``{slice: uint32[words] | None}`` — the host analog of the
        "row" reduce (None = identically-zero result)."""
        with self.ex.tracer.span("hosteval", kind="row", slices=len(slices)):
            self._count("row")
            return self._slice_rows(index, self.ex._rewrite_bsi(index, c), slices)

    def counts(self, index: str, c, slices: list[int]) -> dict:
        """``{slice: int}`` per-slice popcounts ("count" reduce)."""
        with self.ex.tracer.span("hosteval", kind="count", slices=len(slices)):
            self._count("count")
            rows = self._slice_rows(
                index, self.ex._rewrite_bsi(index, c), slices,
                reduce="count",
            )
            return {
                s: (0 if r is None else popcount_words(r))
                for s, r in rows.items()
            }

    def count_total(self, index: str, c, slices: list[int]) -> int:
        """Count(tree) summed over ``slices`` — the host analog of the
        limb total-count (host Python ints are unbounded, so no limb
        split is needed; totals are identical)."""
        return sum(self.counts(index, c, slices).values())

    def agg_partials(self, index: str, rc, slices: list[int]) -> dict:
        """``{slice: int32 partial vector}`` for a rewritten BSI
        aggregate call (BsiSum/BsiMin/BsiMax) — ``ripple.sum_vec`` /
        ``minmax_vec`` through the numpy backend produce the exact
        arrays the fused "agg" programs return, so the executor's
        decode loop is shared verbatim.  Slices with no planes at all
        are omitted (their device batch rows would be all-zero; the
        all-zero partial vector decodes to "no data" identically, so
        emitting it would be equivalent — omission just skips work)."""
        with self.ex.tracer.span("hosteval", kind="agg", slices=len(slices)):
            self._count("agg")
            expr, leaves = plan.decompose(rc)
            t0 = time.monotonic()
            out = {}
            for s in slices:
                rows = [
                    self.ex._leaf_row_host(index, leaf, s) for leaf in leaves
                ]
                if all(
                    r is None
                    for r, leaf in zip(rows, leaves)
                    if leaf.name not in plan.NEUTRAL_LEAVES
                ):
                    continue
                out[s] = np.asarray(
                    plan.eval_expr_np(expr, rows, bp.WORDS_PER_SLICE)
                )
            n_rows = len(slices) * len(leaves)
            if perf_mod.enabled():
                perf_mod.record_launch(
                    "hosteval",
                    reduce="agg",
                    rows=n_rows,
                    n_bytes=perf_mod.plane_bytes(n_rows, bp.WORDS_PER_SLICE),
                    total_ms=(time.monotonic() - t0) * 1e3,
                    trace_id=perf_mod.current_trace_id(),
                )
            return out

    # ------------------------------------------------------------------
    # TopN scoring
    # ------------------------------------------------------------------

    def score_topn_rows(self, frag, lay, src_slot, src_count, tanimoto, min_threshold):
        """``bp.score_rows`` on the fragment's authoritative host plane:
        ``(slots, shared bits)`` of the rows the text keeps, by the same
        rules (``Fragment.score_rows_host``)."""
        with self.ex.tracer.span("hosteval", kind="topn", parts=1):
            self._count("topn")
            t0 = time.monotonic()
            out = frag.score_rows_host(
                lay, src_slot, src_count, tanimoto, min_threshold
            )
            if perf_mod.enabled():
                n_rows = frag.plane_rows()
                perf_mod.record_launch(
                    "hosteval",
                    reduce="topn",
                    rows=n_rows,
                    n_bytes=perf_mod.plane_bytes(n_rows, frag.plane_words()),
                    total_ms=(time.monotonic() - t0) * 1e3,
                    trace_id=perf_mod.current_trace_id(),
                )
            return out

    def score_topn_parts(self, parts) -> None:
        """Fill each TopState's dense count vector HOST-side.

        ``parts``: the executor's score entries ``(st, sub_ref,
        src_words, src_slot, frag)``.  For every dense candidate (the
        positions ``st.dense_pos`` indexes, ids in candidate order),
        the count is ``popcount(row AND src)`` over the fragment's
        authoritative host rows — the arithmetic ``bp.score_planes``
        runs on device, so ``top_score_arrays`` sees identical
        vectors.  An entry prepared without a host copy of its src
        (``src_words`` None: the device reads row ``st.src_row`` from
        the plane) has the src read where the candidates are."""
        with self.ex.tracer.span("hosteval", kind="topn", parts=len(parts)):
            self._count("topn")
            t0 = time.monotonic()
            n_rows = 0
            for st, sub_ref, srcw, _slot, frag in parts:
                if sub_ref is None or st.dense_pos is None:
                    continue
                if srcw is None:
                    srcw = frag._row_words_host(st.src_row)
                ids = st.cand_ids[st.dense_pos]
                counts = np.zeros(len(ids), dtype=np.int32)
                if srcw is not None:
                    src = np.asarray(srcw, dtype=np.uint32)
                    for i, rid in enumerate(ids):
                        row = frag._row_words_host(int(rid))
                        if row is not None:
                            counts[i] = popcount_words(row & src)
                st.counts = counts
                n_rows += len(ids)
            if perf_mod.enabled():
                perf_mod.record_launch(
                    "hosteval",
                    reduce="topn",
                    rows=n_rows,
                    n_bytes=perf_mod.plane_bytes(n_rows, bp.WORDS_PER_SLICE),
                    total_ms=(time.monotonic() - t0) * 1e3,
                    trace_id=perf_mod.current_trace_id(),
                )
