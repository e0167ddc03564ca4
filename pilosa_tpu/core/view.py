"""View — one physical layout of a frame: standard, inverse, or a
time-quantum-generated sub-view.

Owns the fragments for its layout, on disk at
``<frame>/views/<name>/fragments/<slice>`` (reference: view.go:119-188),
routes bit writes by ``columnID // SLICE_WIDTH`` (reference:
view.go:262-279), and notifies the cluster when a write grows the max
slice (reference: view.go:218-250 broadcasting CreateSliceMessage — here
an ``on_create_slice`` callback wired up by the server).

Tiered storage (pilosa_tpu/tier) adds a third fragment state beyond
hot/absent: **cold** — the fragment's metadata is resident here (the
slice counts toward ``max_slice`` and ``fragment_slices``) but its
bytes live as a tar in the object store.  First touch through
:meth:`fragment` or :meth:`create_fragment_if_not_exists` hydrates via
the attached ``hydrator`` (the TierManager); a failed hydration raises
rather than silently serving an empty fragment.
"""

from __future__ import annotations

import os
import sys
import threading
from collections.abc import Callable

from pilosa_tpu.core import cache as cache_mod
from pilosa_tpu.core.fragment import (
    Fragment,
    FragmentRetiredError,
    write_epoch,
)
from pilosa_tpu.obs import trace
from pilosa_tpu.obs.stats import NopStatsClient
from pilosa_tpu.ops.bitplane import SLICE_WIDTH

VIEW_STANDARD = "standard"
VIEW_INVERSE = "inverse"


def is_valid_view(name: str) -> bool:
    """reference: view.go:31-41"""
    return name in (VIEW_STANDARD, VIEW_INVERSE)


def is_inverse_view(name: str) -> bool:
    """Inverse views (incl. time sub-views) share the prefix (reference:
    view.go:43-46)."""
    return name.startswith(VIEW_INVERSE)


class View:
    def __init__(
        self,
        path: str,
        index: str,
        frame: str,
        name: str,
        cache_type: str = cache_mod.TYPE_RANKED,
        cache_size: int = cache_mod.DEFAULT_CACHE_SIZE,
        row_attr_store=None,
        on_create_slice: Callable[[str, str, int], None] | None = None,
    ):
        self.path = path
        self.index = index
        self.frame = frame
        self.name = name
        self.cache_type = cache_type
        self.cache_size = cache_size
        self.row_attr_store = row_attr_store
        self.on_create_slice = on_create_slice
        self.stats = NopStatsClient()  # re-tagged by Frame._new_view
        self.logger = lambda msg: print(msg, file=sys.stderr)  # re-wired alongside stats
        self._mu = threading.RLock()
        self._fragments: dict[int, Fragment] = {}
        # COLD fragments: slice -> opaque store metadata (set by the
        # tier manager).  Metadata resident, bytes in the object store;
        # first touch hydrates through ``hydrator``.  Empty (and
        # hydrator None) on nodes without a configured tier — the hot
        # paths pay one falsy check.
        self._cold: dict[int, object] = {}
        self.hydrator = None  # TierManager, attached with cold entries
        # dense_tier_only()'s last answer and the write epoch it was
        # read at; any fragment content change anywhere retires it.
        self._dense_tier_memo: tuple[int, bool] | None = None

    # --- lifecycle (reference: view.go:97-154) ---

    @property
    def fragments_path(self) -> str:
        return os.path.join(self.path, "fragments")

    def open(self) -> None:
        with self._mu:
            os.makedirs(self.fragments_path, exist_ok=True)
            for entry in sorted(os.listdir(self.fragments_path)):
                if not entry.isdigit():
                    continue  # skip .cache / .snapshotting / strays
                frag = self._new_fragment(int(entry))
                frag.open()
                self._fragments[int(entry)] = frag

    def close(self) -> None:
        with self._mu:
            for frag in self._fragments.values():
                frag.close()
            self._fragments.clear()

    def _new_fragment(self, slice_i: int) -> Fragment:
        frag = Fragment(
            os.path.join(self.fragments_path, str(slice_i)),
            self.index,
            self.frame,
            self.name,
            slice_i,
            cache_type=self.cache_type,
            cache_size=self.cache_size,
        )
        frag.row_attr_store = self.row_attr_store
        frag.stats = self.stats.with_tags(f"slice:{slice_i}")
        frag.cache.stats = frag.stats  # hit/miss/evict counters
        frag.logger = self.logger
        return frag

    # --- accessors ---

    def fragment(self, slice_i: int) -> Fragment | None:
        with self._mu:
            frag = self._fragments.get(slice_i)
            if frag is not None:
                if self.hydrator is not None:
                    self.hydrator.touch(self, slice_i)
                return frag
            if slice_i not in self._cold or self.hydrator is None:
                return None
        # Cold: hydrate OUTSIDE the view lock (store I/O must not hold
        # a core data lock); the hydrator serializes per fragment.
        return self.hydrator.hydrate(self, slice_i)

    def fragments_at(self, slices: list[int]) -> list[Fragment | None]:
        """``[self.fragment(s) for s in slices]`` under ONE acquisition
        of the view lock.  A walk over every slice of an index that
        took the lock per slice made eight concurrent queries queue on
        it 954 times each; once one of them was descheduled inside it
        the queue never cleared (a lock convoy: PERF.md, PR 27)."""
        hydrator = self.hydrator
        # a query takes this lock once a leaf: where it has to wait, the
        # wait is time blocked on purpose (kind ``lock`` in its spans)
        wait = None
        if not self._mu.acquire(blocking=False):
            wait = trace.blocked("lock").begin()
            self._mu.acquire()
            wait.stop()
        try:
            out = [self._fragments.get(s) for s in slices]
            if hydrator is None:
                return out
            cold = []
            for i, s in enumerate(slices):
                if out[i] is not None:
                    hydrator.touch(self, s)
                elif s in self._cold:
                    cold.append(i)
        finally:
            self._mu.release()
            if wait is not None:
                wait.settle()
        for i in cold:  # store I/O outside the view lock, as fragment()
            out[i] = hydrator.hydrate(self, slices[i])
        return out

    def dense_tier_only(self) -> bool:
        """True when no fragment of this view holds a sparse-tier row,
        so every present row is a dense plane (FMT_DENSE) by placement
        — the default budget's whole corpus.  Memoized against the
        process-wide write epoch: a read-mostly load pays one compare,
        and after a write anywhere the next caller asks each hot
        fragment again (no plane is touched).  A cold fragment's tiers
        are unknown until it hydrates, so a view with one answers
        False.  Advisory: callers pick a route from it, never an
        answer."""
        epoch = write_epoch()
        memo = self._dense_tier_memo
        if memo is not None and memo[0] == epoch:
            return memo[1]
        with self._mu:
            frags = list(self._fragments.values())
            unknown = bool(self._cold)
        dense = not unknown and not any(
            f.holds_sparse_tier_rows() for f in frags
        )
        self._dense_tier_memo = (epoch, dense)
        return dense

    def fragments(self) -> list[Fragment]:
        """The HOT (locally materialized) fragments only — cold
        fragments have no local state to flush/close/account."""
        with self._mu:
            return list(self._fragments.values())

    def fragment_slices(self) -> set[int]:
        """Snapshot of the slice numbers that have fragments — hot OR
        cold: a cold fragment's bits must still be found by the
        executor's per-slice walks (the walk's ``fragment()`` call
        hydrates it).  Missing slices contribute nothing to any
        query."""
        with self._mu:
            return set(self._fragments) | set(self._cold)

    def max_slice(self) -> int:
        with self._mu:
            return max(
                max(self._fragments.keys(), default=0),
                max(self._cold.keys(), default=0),
            )

    def create_fragment_if_not_exists(self, slice_i: int) -> Fragment:
        """reference: view.go:218-250"""
        if self.hydrator is not None:
            with self._mu:
                cold = (
                    slice_i in self._cold and slice_i not in self._fragments
                )
            if cold:
                # A WRITE to a cold fragment revives it: hydrate first
                # so the write lands on the full restored plane, never
                # on a silently-empty shadow of it.  Hydration failures
                # raise (loud) — see tier/manager.py.
                frag = self.hydrator.hydrate(self, slice_i)
                if frag is not None:
                    return frag
        notify = False
        with self._mu:
            frag = self._fragments.get(slice_i)
            if frag is not None:
                return frag
            first = len(self._fragments) == 0 and not self._cold
            grew = slice_i > self.max_slice()
            frag = self._new_fragment(slice_i)
            frag.open()
            self._fragments[slice_i] = frag
            notify = (grew or first) and self.on_create_slice is not None
        # OUTSIDE the view lock: the callback crosses into the net
        # layer (the server's gossip CreateSliceMessage broadcast —
        # socket I/O and the gossip mutex must not run under a core
        # data lock).  Found by PILOSA_LOCK_CHECK against the static
        # graph in PR 8; same rule as Fragment.close's listeners.
        if notify:
            # (index, view name, slice) — the view name tells the
            # server whether the new slice is inverse-oriented
            # (reference: view.go:236-241 CreateSliceMessage).
            self.on_create_slice(self.index, self.name, slice_i)
        return frag

    def remove_fragment(self, slice_i: int) -> bool:
        """Drop one fragment from service and DELETE its backing files
        — the rebalance source-release path: the fragment's device
        mirror/sparse rows deregister from the HBM pool (close), and
        its disk footprint returns.  A COLD fragment releases by
        dropping its registration (there are no local bytes).  Returns
        False when the slice has no fragment here."""
        with self._mu:
            frag = self._fragments.pop(slice_i, None)
            was_cold = self._cold.pop(slice_i, None) is not None
        if frag is None:
            return was_cold
        # close() outside the view lock (it notifies close listeners).
        frag.close()
        for path in (frag.path, frag.cache_path):
            try:
                os.unlink(path)
            except OSError:
                pass
        return True

    # --- cold-fragment state (pilosa_tpu/tier) ---

    def register_cold(self, slice_i: int, meta: object) -> bool:
        """Record a cold fragment (bytes in the object store).  No-op
        (False) when a hot fragment already holds the slice."""
        with self._mu:
            if slice_i in self._fragments:
                return False
            self._cold[slice_i] = meta
            return True

    def cold_slices(self) -> set[int]:
        with self._mu:
            return set(self._cold)

    def cold_meta(self, slice_i: int) -> object | None:
        with self._mu:
            return self._cold.get(slice_i)

    def drop_cold(self, slice_i: int) -> None:
        with self._mu:
            self._cold.pop(slice_i, None)

    def _fragment_raw(self, slice_i: int) -> Fragment | None:
        """Plain hot-map lookup — no hydration, no touch.  The
        hydrator's own re-check path."""
        with self._mu:
            return self._fragments.get(slice_i)

    def adopt_hydrated(self, slice_i: int, frag: Fragment) -> None:
        """Install a freshly hydrated fragment and clear its cold
        registration, atomically under the view lock."""
        with self._mu:
            self._fragments[slice_i] = frag
            self._cold.pop(slice_i, None)

    def demote_fragment(
        self,
        slice_i: int,
        meta: object,
        expect: Fragment | None = None,
        expect_version: int | None = None,
    ) -> Fragment | None:
        """Flip a hot fragment to cold: RETIRE it (writes now raise and
        retry through the view, which revives by hydration), pop it,
        and register the store metadata — atomically under the view
        lock.  With ``expect``/``expect_version`` the flip is
        optimistic: it aborts (returns None, fragment stays hot) when
        the fragment was replaced or written since the caller captured
        the version — i.e. since the uploaded tar snapshot — so a
        demotion can never strand a write.  The caller closes the
        returned fragment and deletes its local files outside the
        lock."""
        with self._mu:
            frag = self._fragments.get(slice_i)
            if frag is None:
                return None
            if expect is not None:
                if frag is not expect or not frag.mark_retired_if_version(
                    expect_version or 0
                ):
                    return None
            else:
                frag.mark_retired()
            del self._fragments[slice_i]
            self._cold[slice_i] = meta
            return frag

    # --- writes (reference: view.go:262-279) ---

    def set_bit(self, row_id: int, column_id: int) -> bool:
        # Two attempts: a fragment retired by a concurrent demotion
        # (tier LRU / retention sweep) revives through hydration on the
        # retry; a second failure propagates loudly — a write is never
        # silently dropped into a retired plane.
        last: FragmentRetiredError | None = None
        for _ in range(2):
            frag = self.create_fragment_if_not_exists(
                column_id // SLICE_WIDTH
            )
            try:
                return frag.set_bit(row_id, column_id)
            except FragmentRetiredError as e:
                last = e
        raise last  # type: ignore[misc]

    def clear_bit(self, row_id: int, column_id: int) -> bool:
        last: FragmentRetiredError | None = None
        for _ in range(2):
            frag = self.fragment(column_id // SLICE_WIDTH)
            if frag is None:
                return False
            try:
                return frag.clear_bit(row_id, column_id)
            except FragmentRetiredError as e:
                last = e
        raise last  # type: ignore[misc]
