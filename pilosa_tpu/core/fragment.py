"""Fragment — the storage/compute unit: one (frame, view, slice) bit-plane.

The reference keeps a fragment as an mmap'd roaring bitmap with an
appended op-log, a row cache, a ranked TopN cache, and SHA1 block
checksums for anti-entropy (reference: fragment.go).  The TPU-native
design separates the planes:

* **Authoritative storage** is a host numpy uint32 plane of shape
  (padded_rows, 32768) — bit ``rowID*2^20 + columnID%2^20`` — loaded
  from / persisted to the reference's roaring file format (cookie 12346
  + op-log), so files interoperate with the reference's check/inspect
  and backup tooling.
* **Compute** runs on a lazily-refreshed device mirror of the plane
  (`device_plane()`), so query algebra and TopN scoring execute as
  batched XLA kernels over HBM; the mirror is invalidated by a
  version counter bumped on every mutation.
* **Writes** go to the host plane and append 13-byte ops to the file;
  after MAX_OP_N ops the fragment snapshots: full roaring serialization
  to ``<path>.snapshotting`` atomically renamed over the data file
  (reference: fragment.go:1006-1074).

TopN keeps the reference's ranked-cache candidate selection but scores
all candidates in one batched kernel and selects on host, instead of the
reference's sequential per-row loop with threshold pruning
(reference: fragment.go:505-639) — same results, hardware-shaped loop.
"""

from __future__ import annotations

import fcntl
import hashlib
import io
import itertools
import json
import mmap
import os
import sys
import tarfile
import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from collections.abc import Iterable, Sequence
from typing import Any

import numpy as np

from pilosa_tpu import device as device_mod
from pilosa_tpu.core import cache as cache_mod
from pilosa_tpu.ingest import scatter as ingest_scatter
from pilosa_tpu.ingest import wal as ingest_wal
from pilosa_tpu.core.bitmap import RowBitmap
from pilosa_tpu.core.cache import Pair
from pilosa_tpu.obs.stats import NopStatsClient
from pilosa_tpu.ops import bitplane as bp
from pilosa_tpu.ops import roaring

SLICE_WIDTH = bp.SLICE_WIDTH

# reference: fragment.go:58-65
HASH_BLOCK_SIZE = 100
DEFAULT_FRAGMENT_MAX_OP_N = 2000
# Dense-tier budget: the device-mirrored dense plane (the batched-kernel
# fast path) holds rows up to DENSE_PLANE_BYTES: 65,536 rows of a plane
# whose rows span the whole slice (128 KiB each), 16.8M rows of one whose
# columns end at 4,096 (512 B each: ``bp.row_words``).  Rows beyond
# the budget live in the SPARSE tier as sorted uint32 offset arrays,
# paying only for set bits — the dense-plane analog of roaring's
# pay-per-container storage (reference: roaring/roaring.go:43-52), so
# tall-sparse fragments (inverse views, where the row axis is the
# column space — up to 2^20 distinct rows per slice) are unbounded.
DENSE_ROW_BUDGET = 1 << 16
DENSE_PLANE_BYTES = DENSE_ROW_BUDGET * bp.WORDS_PER_SLICE * 4
# Sparse rows whose bit count crosses this are promoted to the dense
# tier when budget remains: past it, offset arrays (4 B/bit) cost more
# than the 128 KiB plane row.
PROMOTE_BITS = 32 * 1024
# Paged-to-device sparse rows kept per fragment (LRU, 128 KiB each).
SPARSE_DEVICE_CACHE = 64
# Device bytes of one paged row (uint32[WORDS_PER_SLICE]).
ROW_NBYTES = bp.WORDS_PER_SLICE * 4
# Largest legal row id: op-log positions are u64 and pos = row*2^20+off.
MAX_ROW_ID = 1 << 44

# Process-wide mutation epoch: bumped on EVERY fragment content change
# (point writes, bulk imports, restores).  Read-side caches (the
# executor's assembled leaf batches) validate in O(1) against it and
# only fall back to per-fragment version checks when it moved —
# read-mostly query workloads never pay a per-slice validation walk.
_write_epoch = 0


def _bump_write_epoch() -> None:
    global _write_epoch
    _write_epoch += 1


def write_epoch() -> int:
    return _write_epoch


_fragment_serials = itertools.count(1)

# Fragment-close listeners: bound methods (held weakly, so an executor
# that is never close()d still gets collected) called with the fragment
# when it leaves service — shutdown or frame/index deletion.  Read-side
# caches that pin per-fragment device memory (the executor's TopN prep
# cache) drop their entries here instead of waiting for LRU
# displacement.
_close_listeners: "list" = []
_close_listeners_mu = threading.Lock()


def register_close_listener(method) -> None:
    import weakref

    with _close_listeners_mu:
        _close_listeners.append(weakref.WeakMethod(method))


def unregister_close_listener(method) -> None:
    with _close_listeners_mu:
        _close_listeners[:] = [
            wm for wm in _close_listeners if wm() not in (None, method)
        ]


def _notify_close(frag) -> None:
    with _close_listeners_mu:
        listeners = [wm() for wm in _close_listeners]
        if None in listeners:  # drop collected entries opportunistically
            _close_listeners[:] = [wm for wm in _close_listeners if wm() is not None]
    for fn in listeners:
        if fn is None:
            continue
        try:
            fn(frag)
        except Exception:  # noqa: BLE001 — listeners must not break close
            pass


# Fragment WRITE listeners: called with (fragment, set_rows, set_cols,
# clear_rows, clear_cols, exact) — absolute column ids — after every
# successful content change (point writes, bulk imports, sync merges).
# ``exact`` is True only when every reported bit provably CHANGED state
# (the point-write paths, which skip notification on no-ops); bulk
# imports report the requested lists, which may include already-set
# bits, so incremental consumers (the subscribe delta engine) must
# treat exact=False entries as dirtiness, not arithmetic.  The
# rebalance delta log rides this hook to capture the write stream of a
# migrating slice; when nothing is registered the cost is one
# list-truthiness check per write.  Listeners register module-wide
# (every fragment) or per-fragment (Fragment.add_write_listener);
# per-fragment listeners are dropped automatically when the fragment
# leaves service (close/retire) so churning subscribers cannot leak
# callbacks on rebalanced-away slices.
_write_listeners: list = []
_write_listeners_mu = threading.Lock()


def register_write_listener(fn) -> None:
    with _write_listeners_mu:
        if fn not in _write_listeners:
            _write_listeners.append(fn)


def unregister_write_listener(fn) -> None:
    with _write_listeners_mu:
        _write_listeners[:] = [f for f in _write_listeners if f is not fn]


def _notify_write(
    frag, set_rows, set_cols, clear_rows, clear_cols, exact=False
) -> None:
    if frag._wal_replaying:
        # WAL recovery re-applies writes the listeners (replication,
        # rebalance delta log, subscriptions) already saw acked before
        # the crash — fanning them out again would double-count.
        return
    for fn in list(_write_listeners) + list(frag._frag_write_listeners):
        try:
            fn(frag, set_rows, set_cols, clear_rows, clear_cols, exact)
        except Exception:  # noqa: BLE001 — listeners must not break writes
            pass


def _apply_pending(dev, pending):
    """Fold queued point writes into one device scatter.

    Sequential semantics per bit compose to last-wins: each (slot, word)
    accumulates a set-mask and clear-mask where a later opposite op on
    the same bit cancels the earlier one, then a single gather/modify/
    scatter applies ``(v & ~clear) | set`` — unique keys, so the scatter
    never races."""
    acc: dict[tuple[int, int], list[int]] = {}
    for slot, word, mask, op in pending:
        masks = acc.setdefault((slot, word), [0, 0])
        if op:
            masks[0] |= mask
            masks[1] &= ~mask
        else:
            masks[1] |= mask
            masks[0] &= ~mask
    keys = list(acc)
    slots = np.asarray([k[0] for k in keys], dtype=np.int32)
    words = np.asarray([k[1] for k in keys], dtype=np.int32)
    set_m = np.asarray([acc[k][0] for k in keys], dtype=np.uint32)
    keep_m = np.asarray(
        [(~acc[k][1]) & 0xFFFFFFFF for k in keys], dtype=np.uint32
    )
    cur = dev[slots, words]
    return dev.at[slots, words].set((cur & keep_m) | set_m)


class FragmentError(RuntimeError):
    pass


class FragmentRetiredError(FragmentError):
    """A write landed on a fragment that left service (demoted to the
    cold tier or released after migration).  Raised instead of
    mutating the orphaned in-memory plane — the caller (View.set_bit)
    retries through the view, which revives the fragment by
    hydration; a second failure propagates loudly.  Bits are never
    silently dropped."""


class ArchiveChecksumError(FragmentError):
    """A fragment tar's payload does not match its embedded per-entry
    checksum — the named error restore paths reject on instead of
    silently installing torn bytes (the tar self-verifies since the
    tiered-storage PR; rebalance's out-of-band checksums remain)."""


@dataclass
class PairSet:
    """Parallel row/column id lists for block sync (reference:
    fragment.go:1509-1512)."""

    row_ids: list[int] = field(default_factory=list)
    column_ids: list[int] = field(default_factory=list)


@dataclass
class TopOptions:
    """reference: fragment.go:675-691"""

    n: int = 0
    src: RowBitmap | None = None
    row_ids: list[int] | None = None
    min_threshold: int = 0
    filter_field: str = ""
    filter_values: list[Any] | None = None
    tanimoto_threshold: int = 0

    @property
    def keeps_every_counted(self) -> bool:
        """Whether candidate filtering (``Fragment._filter_arrays``)
        reduces to ``count > 0`` under these options, whatever the src:
        no count window, no threshold an integer count above 0 could
        miss, no attr filter."""
        return (
            not self.tanimoto_threshold
            and self.min_threshold <= 1
            and not (self.filter_field and self.filter_values)
        )


@dataclass
class TopState:
    """In-flight TopN work on one fragment, between top_prepare (async
    kernel dispatch) and top_finish (fetch + selection) — array-native:
    candidate ids / cached counts are int64 ndarrays in candidate
    (count-descending) order, and the dense/sparse score tiers are
    POSITIONS into that order.  ``done_ids``/``done_cnts`` short-circuit
    the src-less / empty cases with a final (filtered, sorted, trimmed)
    result; otherwise ``dev_counts`` holds the un-fetched device score
    vector (the executor may bulk-fetch many fragments' vectors in one
    round trip and hand the result back via ``counts``)."""

    done_ids: np.ndarray | None = None
    done_cnts: np.ndarray | None = None
    cand_ids: np.ndarray | None = None
    cand_cached: np.ndarray | None = None
    dense_pos: np.ndarray | None = None
    sparse_pos: np.ndarray | None = None
    sparse_cnt: np.ndarray | None = None
    n: int = 0
    tanimoto: int = 0
    src_count: int = 0
    min_threshold: int = 0
    dev_counts: object = None
    counts: object = None
    # The src is this row of the fragment's own plane and no host copy
    # of it was made (top_prepare_own_parts): the device scorer reads
    # it at its slot, the host scorer by this id.
    src_row: int | None = None


@dataclass
class SubRef:
    """One fragment's TopN scoring inputs: the executor feeds ``plane``
    (the HBM-resident mirror) and ``slots`` (padded candidate slot
    indices) straight into one fused cross-fragment program
    (bp.score_planes) — no gathered candidate copy ever exists on
    device (an eager per-fragment/stacked copy once tripped OOM at 100
    slices x 256 candidates).  ``plane`` is the mirror ARRAY captured
    under the fragment lock at prepare time: jax arrays are immutable
    and mirror refreshes create new objects, so the captured reference
    is a free content snapshot — dense scoring stays consistent with
    the sparse-tier probes even if a writer lands before the program
    runs."""

    plane: object  # device plane mirror (immutable array snapshot)
    slots: np.ndarray  # int32[padded_rows] candidate slot indices
    shape: tuple  # (padded_rows, words)
    plane_rows: int  # mirror row count (program-shape grouping)
    device: object


@dataclass(frozen=True, eq=False)
class TopLayout:
    """The gather layout of a fragment's OWN ranked candidates: what a
    TopN scoring pass over exactly those rows needs and no query text
    changes.  ``ids`` / ``cnts`` are the rank cache's listing (count
    falling, ids rising) less the rows it counts empty; ``dense_pos`` /
    ``sparse_pos`` their positions by row tier; ``slots`` the padded
    int32 slot vector a SubRef carries (None without a dense-tier
    candidate).  Made by ``Fragment.top_layout`` and valid for the
    fragment ``version`` and the rank-cache arrays ``ranked`` it was
    made from; the arrays are shared by every query that uses it and
    are never written."""

    ids: np.ndarray
    cnts: np.ndarray
    dense_pos: np.ndarray
    sparse_pos: np.ndarray
    slots: np.ndarray | None
    version: int
    ranked: tuple


@dataclass(frozen=True, eq=False)
class RowsLayout:
    """A fragment's ranked candidates BY PLANE SLOT, for the scorer that
    walks every row of the plane (``bp.score_rows``): ``ids`` int64[plane
    rows], the row in each slot (-1: none); ``cnts`` the ranked cache's
    count of it as an int32 device array beside the mirror (0: no
    candidate — an empty slot, a row the cache does not rank);
    ``window`` the ranked counts ascending, which answer "how many
    candidates does a count window keep" by two binary searches.  Made
    by ``Fragment.rows_layout`` and valid for the fragment ``version``
    and the rank-cache arrays ``ranked`` it was made from; shared by
    every query that uses it and never written."""

    ids: np.ndarray
    cnts: object
    window: np.ndarray
    version: int
    ranked: tuple


class Fragment:
    """One frame-view x slice bit-plane with caches and sync hooks."""

    def __init__(
        self,
        path: str,
        index: str,
        frame: str,
        view: str,
        slice_i: int,
        cache_type: str = cache_mod.TYPE_RANKED,
        cache_size: int = cache_mod.DEFAULT_CACHE_SIZE,
        max_op_n: int = DEFAULT_FRAGMENT_MAX_OP_N,
        dense_row_budget: int | None = None,
    ):
        self.path = path
        self.index = index
        self.frame = frame
        self.view = view
        self.slice = slice_i
        self.cache_type = cache_type
        self.cache_size = cache_size
        self.max_op_n = max_op_n
        # Rows of the dense tier; None (every caller but a test): as
        # many as DENSE_PLANE_BYTES holds at the plane's row width.
        self.dense_row_budget = dense_row_budget

        self.row_attr_store = None  # wired by Frame
        self.stats = NopStatsClient()  # re-tagged by View._new_fragment
        # Injectable like Handler's (net/handler.py): embedders route or
        # silence repair notices; default matches the CLI server.
        self.logger = lambda msg: print(msg, file=sys.stderr)
        # Process-unique identity for cache version vectors: unlike
        # id(), a serial is never reused by a recreated fragment.
        self._serial = next(_fragment_serials)
        # Residency-pool identities (device/pool.py): the dense-plane
        # HBM mirror and the paged-sparse-row cache account separately.
        self._pool_key = ("frag", self._serial, "mirror")
        self._sparse_pool_key = ("frag", self._serial, "sparse")

        self._mu = threading.RLock()
        # Two-tier row storage.  DENSE: plane row *slots* hold up to
        # dense_row_budget touched rows (device-mirrored fast path);
        # _slot_of maps logical row id -> slot.  SPARSE: every further
        # row is a sorted uint32 array of in-slice bit offsets — memory
        # scales with set bits, so fragments are row-unbounded.  A
        # plane row is as many words as the columns the fragment holds
        # ask for (bp.row_words: a pow2 class, derived from the data
        # alone); a write beyond it re-lays the plane (_relayout_locked).
        self._plane = bp.empty_plane(bp.ROW_BLOCK, bp.MIN_ROW_WORDS)
        self._slot_of: dict[int, int] = {}
        self._sparse: dict[int, np.ndarray] = {}
        # Sparse rows paged to the home device for query leaves (LRU).
        # Each entry holds the row's COMPRESSED container payload —
        # (fmt, device_payload, encoded_nbytes) per ops/bitplane
        # encode_row — so HBM residency scales with cardinality, not
        # with the 128 KiB dense geometry; _sparse_dev_nbytes tracks
        # the resident total for pool accounting.
        self._sparse_dev: "OrderedDict[int, tuple]" = OrderedDict()
        self._sparse_dev_nbytes = 0
        # Host-side encoded payloads (write-time format selection),
        # invalidated per row by _after_write like _row_cache; bytes
        # are the compressed size, so the cache is cheap even for the
        # row-unbounded sparse tier.
        self._payload_cache: dict[int, tuple] = {}
        # TopN candidate-row gathers cached per (version, candidate set):
        # Sorted tier-key arrays for vectorized dense/sparse candidate
        # splits (see _tier_key_arrays_locked), cached per version.
        self._tier_arrays = None
        self._tier_arrays_version = -1
        # Gather layout of the ranked candidates (see top_layout), kept
        # until a write or a rank-cache re-sort; and the same candidates
        # by plane slot, for the scorer that walks rows (rows_layout).
        self._top_layout: TopLayout | None = None
        self._rows_layout: RowsLayout | None = None
        self._rows_pool_key = ("frag", self._serial, "rowcounts")
        self._max_row_id = 0
        self._op_n = 0
        self._version = 0
        # Per-fragment write listeners (add_write_listener): cleared on
        # close/retire so a fragment leaving service holds zero
        # registered callbacks (no leak across rebalance or tier churn).
        self._frag_write_listeners: list = []
        # Incremental per-row popcounts (reference keeps cached counts,
        # bitmap.go:184-217); avoids an O(row) recount on every SetBit.
        self._count_of: dict[int, int] = {}
        self._device = None
        self._device_version = -1
        # Point writes queue here while a device mirror exists; the next
        # read folds them into ONE batched scatter instead of re-uploading
        # the whole plane (SURVEY.md §7 "mutation rate vs immutable device
        # buffers").  (slot, word, mask, op) with op 1=OR / 0=ANDNOT.
        self._device_pending: list[tuple[int, int, int, int]] = []
        # Slots with queued deltas — lets device_row() serve a row the
        # pending writes DON'T touch straight from the resident mirror
        # (byte-exact: every plane change since the last sync is in the
        # queue).  Maintained strictly alongside _device_pending.
        self._pending_slots: set[int] = set()
        self._file = None
        # Group-commit op-log buffer: point writes append 13-byte op
        # records here and fsync-free flush happens at boundaries
        # (threshold / snapshot / close / holder flush loop) instead of
        # per bit.  The reference gets the same effect from writing ops
        # into an mmap'd file and letting the page cache carry them
        # (reference: fragment.go:379-418, roaring/roaring.go:649-660);
        # durability is identical-in-kind: a crash can lose ops since
        # the last flush boundary, never committed state.  Reads never
        # consult the file while open, so read-your-writes holds.
        self._op_buf = bytearray()
        # Durable-ingest hooks (pilosa_tpu/ingest): a WAL writer is
        # attached at open when an IngestManager owns this path; while
        # attached, every changed op ALSO appends to the WAL and acks
        # can wait on its group-commit fsync.  _wal_replaying marks
        # crash-recovery replay (suppresses listener fanout, WAL
        # re-logging, and mid-replay auto-snapshots).
        self._wal = None
        self._wal_replaying = False
        self._row_cache: dict[int, np.ndarray] = {}
        self.cache = cache_mod.new_cache(cache_type, cache_size)
        # Block checksum cache: blocks() re-hashes only blocks written
        # since the last call (the reference likewise caches block
        # checksums and invalidates per-write, fragment.go:717-796).
        # A None digest records "materialized but empty" (skipped).
        self._block_sums: dict[int, bytes | None] = {}
        self._dirty_blocks: set[int] = set()
        self._opened = False
        # Set by retire(): the fragment left service (tier demotion,
        # post-migration release) and writes must raise rather than
        # mutate the orphaned plane.  Reads stay valid — the host
        # tiers still hold the content as of retirement.
        self._retired = False

    # ------------------------------------------------------------------
    # lifecycle (reference: fragment.go:154-338)
    # ------------------------------------------------------------------

    def open(self) -> None:
        with self._mu:
            if self._opened:
                return
            os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
            self._file = open(self.path, "a+b")
            try:
                fcntl.flock(self._file.fileno(), fcntl.LOCK_EX | fcntl.LOCK_NB)
            except OSError as e:
                self._file.close()
                self._file = None
                raise FragmentError(f"fragment file locked: {self.path}") from e
            try:
                self._open_storage()
                self._open_cache()
            except BaseException:
                # A failed open must not leave the file locked — the
                # flock would block every retry (and any other Fragment
                # on the path) until process exit.
                fcntl.flock(self._file.fileno(), fcntl.LOCK_UN)
                self._file.close()
                self._file = None
                raise
            self._version += 1
            self._opened = True
            # Durable ingest: replay any WAL tail newer than the
            # snapshot+op-log state just loaded, then attach a writer
            # (no-op when no IngestManager owns this path).  Inside
            # _mu: lock order is frag._mu -> wal locks.
            ingest_wal.attach_fragment(self)

    def _open_storage(self) -> None:
        size = os.fstat(self._file.fileno()).st_size
        if size == 0:
            # Seed an empty roaring header so subsequent op-log appends
            # produce a parseable file (reference: fragment.go:187-242
            # unmarshals the file before attaching the op writer).
            self._file.write(roaring.encode({}))
            self._file.flush()
            return
        # Streaming load straight out of an mmap of the file
        # (_load_direct): containers fill the two tiers in place, no
        # whole-file intermediate, so peak RSS on open is the TIER
        # size, not 2x the file (reference mmaps and zero-copies
        # containers, fragment.go:154-242, roaring/roaring.go:567-620).
        # Array containers stay as value arrays, so a tall-sparse
        # file loads in O(set bits).
        mm = mmap.mmap(self._file.fileno(), 0, access=mmap.ACCESS_READ)
        err = None
        try:
            op_n = self._load_direct(mm)
        except roaring.CorruptError as e:
            # A decode failure's traceback frames hold buffer
            # views of the mmap; closing it here would raise
            # BufferError and mask the corruption diagnosis.
            # Capture the message, let the except block drop the
            # traceback (and with it the views), then close and
            # re-raise cleanly.
            err = str(e)
        if err is not None:
            # WAL recovery: a crash mid-append (group commit makes the
            # torn window up to the flush buffer, not one record) leaves
            # a tail that fails its FNV checks.  Truncate to the last
            # valid record and serve the committed prefix; anything that
            # is NOT pure-tail damage still refuses to load (reference
            # replays ops on open, roaring/roaring.go:622-646 — its
            # single-record appends make torn tails near-impossible, so
            # it has no repair; ours must).
            torn = None
            try:
                # The bound follows THIS fragment's group-commit flush
                # threshold (a subclass/test may tune it): crash residue
                # can never exceed one flush buffer + the record that
                # tripped it.
                torn = roaring.scan_torn_tail(
                    mm, max_tail=self._OP_FLUSH_BYTES + 2 * roaring.OP_SIZE
                )
            except roaring.CorruptError:
                torn = None
            op_n = None
            if torn is not None:
                # Prove the committed prefix actually loads BEFORE
                # mutating the file — damage outside the op tail (e.g. a
                # corrupt container payload alongside tail garbage) must
                # leave the file bytes untouched for forensics, not get
                # half-"repaired" and still refuse to open.  _load_direct
                # only commits to self on success and copies everything
                # it keeps, so the view/mmap can close right after.
                view = memoryview(mm)[: torn[0]]
                try:
                    op_n = self._load_direct(view)
                except roaring.CorruptError:
                    op_n = None
                finally:
                    del view
            mm.close()
            if op_n is None:
                raise roaring.CorruptError(err)
            valid_end, reason = torn
            dropped = size - valid_end
            self._file.truncate(valid_end)
            self._file.flush()
            os.fsync(self._file.fileno())
            self.stats.count("oplogRepair")
            self.logger(
                f"fragment {self.path}: repaired torn op-log tail "
                f"({reason}); dropped {dropped} uncommitted bytes"
            )
        else:
            mm.close()
        # replayed-op count feeds snapshot bookkeeping
        self._op_n = op_n

    def add_write_listener(self, fn) -> None:
        """Register a write listener on THIS fragment only (same call
        signature as the module-wide hook).  Dropped automatically when
        the fragment leaves service — close, retire, tier demotion —
        so callers need no unhook path for slices that churn away."""
        with self._mu:
            if fn not in self._frag_write_listeners:
                self._frag_write_listeners.append(fn)

    def remove_write_listener(self, fn) -> None:
        with self._mu:
            self._frag_write_listeners[:] = [
                f for f in self._frag_write_listeners if f is not fn
            ]

    def write_listener_count(self) -> int:
        with self._mu:
            return len(self._frag_write_listeners)

    def close(self) -> None:
        with self._mu:
            if self._wal is not None:
                # Final group commit + file close; pending waiters
                # resolve durable (or WalClosed if the commit fails).
                writer, self._wal = self._wal, None
                writer._manager.detach(writer)
            if self._file is not None:
                self._flush_ops_locked()
                self.flush_cache()
                fcntl.flock(self._file.fileno(), fcntl.LOCK_UN)
                self._file.close()
                self._file = None
            # Explicit HBM release: drop the mirror AND the paged sparse
            # rows, and deregister both from the residency pool — a
            # deleted frame or an in-process restart returns its device
            # bytes now, not whenever GC reaches self._device.
            self._invalidate_device()
            self._sparse_dev.clear()
            self._sparse_dev_nbytes = 0
            self._payload_cache.clear()
            device_mod.pool().remove(self._sparse_pool_key)
            self._rows_layout = None
            device_mod.pool().remove(self._rows_pool_key)
            self._opened = False
            # A fragment leaving service (shutdown OR frame/index/view
            # deletion) must invalidate epoch-validated read caches —
            # deletes would otherwise serve stale batches until some
            # unrelated write moved the epoch.
            _bump_write_epoch()
            # A closed fragment must hold zero registered listeners —
            # per-fragment callbacks die with the fragment's service
            # life, never with its garbage collection.
            self._frag_write_listeners.clear()
        # Outside the lock: listeners may take their own locks.
        _notify_close(self)

    def retire(self) -> None:
        """Take the fragment out of service permanently: block further
        writes (they raise :class:`FragmentRetiredError` so the caller
        revives through the view instead of losing bits), then close.
        The tier manager's demotion path calls this AFTER the tar
        upload verified, so retirement never strands unuploaded
        state."""
        self.mark_retired()
        self.close()

    def mark_retired(self) -> None:
        with self._mu:
            self._retired = True
            # Retirement blocks writes permanently, so per-fragment
            # write listeners can never fire again — drop them now.
            self._frag_write_listeners.clear()

    def mark_retired_if_version(self, version: int) -> bool:
        """Atomically retire ONLY if no write landed since ``version``
        was read — the optimistic token the tier demotion path uses:
        the uploaded tar snapshot is provably current when this
        succeeds, and any write racing the demotion either bumped the
        version first (demotion aborts) or arrives after retirement
        (raises, and the view-level retry revives by hydration)."""
        with self._mu:
            if self._version != version:
                return False
            self._retired = True
            return True

    def _check_writable_locked(self) -> None:
        if self._retired:
            raise FragmentRetiredError(
                f"fragment {self.index}/{self.frame}/{self.view}/"
                f"{self.slice} is retired (demoted or released); "
                "re-resolve it through the view"
            )

    @property
    def cache_path(self) -> str:
        """reference: fragment.go:147-149"""
        return self.path + ".cache"

    def _open_cache(self) -> None:
        """Load persisted TopN candidate ids and re-count their rows
        (reference: fragment.go:244-282)."""
        try:
            with open(self.cache_path, "rb") as fh:
                payload = fh.read()
        except FileNotFoundError:
            return
        except OSError:
            return  # corrupt cache is rebuilt lazily, like the reference
        ids = self._decode_cache_ids(payload)
        if ids is None:
            return
        for row_id in ids:
            if isinstance(row_id, int) and (
                row_id in self._slot_of or row_id in self._sparse
            ):
                self.cache.bulk_add(row_id, self._count_of.get(row_id, 0))
        self.cache.invalidate()

    @staticmethod
    def _encode_cache_ids(ids: list[int]) -> bytes:
        """The reference's protobuf ``Cache`` message (same name + field
        number as internal/private.proto, reference: fragment.go:
        1083-1110) — .cache files and backup-tar "cache" entries are
        interchangeable with a real Pilosa's."""
        from pilosa_tpu.net import wire_pb2 as wire

        return wire.Cache(IDs=ids).SerializeToString()

    @staticmethod
    def _decode_cache_ids(payload: bytes) -> list[int] | None:
        """Cache-file payload -> row ids.  Protobuf ``Cache`` is the
        format; a leading '[' means a JSON list from r01-r04 files
        (kept readable for upgrades).  None = unreadable (the cache
        rebuilds lazily, like the reference)."""
        if payload[:1] == b"[":
            try:
                ids = json.loads(payload)
            except json.JSONDecodeError:
                return None
            return ids if isinstance(ids, list) else None
        from pilosa_tpu.net import wire_pb2 as wire

        msg = wire.Cache()
        try:
            msg.ParseFromString(payload)
        except Exception:
            return None
        return list(msg.IDs)

    def flush_cache(self) -> None:
        """Persist TopN candidate row ids (reference: fragment.go:1083-1110)."""
        with self._mu:
            tmp = self.cache_path + ".tmp"
            with open(tmp, "wb") as fh:
                fh.write(self._encode_cache_ids(self.cache.ids()))
            os.replace(tmp, self.cache_path)

    # ------------------------------------------------------------------
    # geometry
    # ------------------------------------------------------------------

    def pos(self, row_id: int, column_id: int) -> int:
        """Bit position within the plane (reference: fragment.go:476-484,
        1529-1531)."""
        min_col = self.slice * SLICE_WIDTH
        if not (min_col <= column_id < min_col + SLICE_WIDTH):
            raise FragmentError(
                f"column out of bounds: {column_id} not in slice {self.slice}"
            )
        return row_id * SLICE_WIDTH + (column_id % SLICE_WIDTH)

    @property
    def max_row_id(self) -> int:
        return self._max_row_id

    def _dense_cap(self, words: int | None = None) -> int:
        """Rows the dense tier may hold at plane rows of ``words`` words
        (the plane's own when None)."""
        if self.dense_row_budget is not None:
            return self.dense_row_budget
        return DENSE_PLANE_BYTES // (4 * (words or self._plane.shape[1]))

    def _ensure_width_locked(self, max_offset: int) -> None:
        """Make the plane's rows cover in-slice column ``max_offset``."""
        words = bp.row_words(max_offset)
        if words > self._plane.shape[1]:
            self._relayout_locked(words)

    def _relayout_locked(self, words: int) -> None:
        """Re-lay the dense plane at rows of ``words`` words: a write
        beyond the columns the plane covered.  Rare (the width is a pow2
        class: at most eight times in a fragment's life) and structural:
        the mirror is dropped and the version moves.  Rows the byte
        budget no longer holds at the new width move to the sparse
        tier, last slots first, so one far column never turns a million
        512 B rows into a million 128 KiB ones."""
        old = self._plane
        keep = min(len(self._slot_of), self._dense_cap(words))
        demoted = keep < len(self._slot_of)
        if demoted:
            for row_id, slot in list(self._slot_of.items()):
                if slot >= keep:
                    del self._slot_of[row_id]
                    self._sparse[row_id] = bp.np_row_to_columns(old[slot]).astype(
                        np.uint32
                    )
                    self._row_cache.pop(row_id, None)
        rows = bp.pad_rows(max(keep, 1)) if demoted else old.shape[0]
        plane = bp.empty_plane(rows, words)
        n = min(rows, old.shape[0])
        plane[:n, : old.shape[1]] = old[:n]
        self._plane = plane
        self._tier_arrays = None
        if self._slot_of:
            self.stats.count("fragment.relayouts")
        if self._device is not None:
            ingest_scatter.note_fallback()
        self._invalidate_device()
        self._version += 1
        _bump_write_epoch()

    def _ensure_slot(self, row_id: int) -> int | None:
        """Dense-tier slot for a row, or None when the row lives in (or
        a first touch lands in) the SPARSE tier.  Dense capacity is
        allocated compactly up to ``dense_row_budget``; beyond it new
        rows start sparse — memory scales with set bits, never with
        distinct-row count (the roaring pay-per-container analog)."""
        slot = self._slot_of.get(row_id)
        if slot is not None:
            return slot
        if row_id in self._sparse:
            return None
        # Bit positions are u64 in the op-log (pos = row*2^20 + offset),
        # so row ids must stay below 2^44; reject before mutating state
        # (PQL rowID=-1 wraps to 2^64-1 at the executor boundary).
        if row_id >= MAX_ROW_ID:
            raise FragmentError(f"row id out of range: {row_id}")
        self._max_row_id = max(self._max_row_id, row_id)
        if len(self._slot_of) >= self._dense_cap():
            self._sparse[row_id] = np.empty(0, dtype=np.uint32)
            self._count_of[row_id] = 0
            return None
        slot = self._alloc_dense_slot(row_id)
        self._count_of[row_id] = 0
        return slot

    def _alloc_dense_slot(self, row_id: int) -> int:
        slot = len(self._slot_of)
        self._slot_of[row_id] = slot
        needed = bp.pad_rows(slot + 1)
        if needed > self._plane.shape[0]:
            self._reserve_dense(
                max(needed, min(2 * self._plane.shape[0], self._dense_cap()))
            )
        return slot

    def _reserve_dense(self, n_slots: int) -> None:
        """Grow the dense plane to hold ``n_slots`` rows in ONE
        allocation.  Bulk imports pre-size for all their new rows up
        front — growing through the doubling path copies the whole
        plane O(log n) times."""
        needed = bp.pad_rows(max(n_slots, 1))
        if needed > self._plane.shape[0]:
            extra = np.zeros(
                (needed - self._plane.shape[0], self._plane.shape[1]), np.uint32
            )
            self._plane = np.vstack([self._plane, extra])
            # the device mirror no longer matches the plane's shape —
            # a structural change the delta-scatter cannot express
            if self._device is not None:
                ingest_scatter.note_fallback()
            self._invalidate_device()

    def _maybe_promote(self, row_id: int) -> None:
        """Sparse rows past PROMOTE_BITS move to the dense tier while
        budget remains (beyond it, offset arrays cost more than the
        plane row); correctness never depends on promotion."""
        offs = self._sparse.get(row_id)
        if (
            offs is None
            or len(offs) <= PROMOTE_BITS
            or len(self._slot_of) >= self._dense_cap(bp.row_words(int(offs[-1])))
        ):
            return
        self._ensure_width_locked(int(offs[-1]))
        del self._sparse[row_id]
        self._payload_cache.pop(row_id, None)
        if self._sparse_dev.pop(row_id, None) is not None:
            self._sync_sparse_pool_locked()
        slot = self._alloc_dense_slot(row_id)
        self._tier_arrays = None
        self._plane[slot] = bp.np_columns_to_row(offs, self._plane.shape[1])
        # Tier promotion rewrites a whole plane row — structural, not a
        # per-bit delta the scatter path can carry.
        if self._device is not None:
            ingest_scatter.note_fallback()
        self._invalidate_device()

    def _load_direct(self, mm) -> int:
        """Stream containers from the mmap'd file STRAIGHT into the two
        storage tiers and replay the op-log; returns the op count.

        Unlike decode_tiered + _load_tiered (kept for restore payloads),
        no whole-file container dict ever materializes, so open's peak
        heap is the tier size itself (plane + sparse offsets ≈ file
        bytes), not 2x — the closest Python analog of the reference's
        zero-copy mmap container attach (roaring/roaring.go:567-620):
        file bytes stay in the page cache, the heap holds exactly the
        tiers.  Everything builds into locals and commits to ``self`` at
        the end, so a CorruptError mid-parse leaves the fragment's state
        untouched (the torn-tail repair path retries after truncating).
        """
        keys, ns, offs, plens, ops_base = roaring.parse_header_tables(mm)
        size = len(mm)
        cps = bp.CONTAINERS_PER_SLICE
        cbits = roaring.CONTAINER_BITS
        wpc = bp.WORDS_PER_CONTAINER
        n_cont = len(keys)

        if n_cont:
            ends = offs + plens
            if (offs >= size).any() or (ends > size).any():
                raise roaring.CorruptError("container payload out of bounds")
            if (offs % 4).any():
                raise roaring.CorruptError("misaligned container payload")
            ops_offset = int(max(ops_base, ends.max()))
        else:
            ops_offset = ops_base

        rows_of = (keys // cps).astype(np.int64)
        # Header n fields drive the density RANKING only; exact counts
        # are recomputed from the actual payloads after the tiers are
        # built (a corrupt n must never poison Count/TopN — the check
        # CLI reports such files, but open stays payload-truthful).
        uniq_rows, starts = np.unique(rows_of, return_index=True)
        row_counts = (
            np.add.reduceat(ns, starts) if n_cont else np.zeros(0, np.int64)
        )
        order = np.argsort(-row_counts, kind="stable")

        # One u32 view over the payload region (no copy; op-log records
        # after ops_offset are 13-byte and break 4-alignment, so the
        # view stops there).
        u32 = np.frombuffer(mm, dtype="<u4", count=ops_offset // 4)

        amask = ns <= roaring.ARRAY_MAX_SIZE if n_cont else np.zeros(0, bool)
        bmask = ~amask if n_cont else amask

        # The plane's row width, from the highest column a container
        # holds: a bitmap container counts whole, an array container to
        # its last (largest) value.
        words = bp.MIN_ROW_WORDS
        if n_cont:
            cidx_all = (keys % cps).astype(np.int64)
            top = np.flatnonzero(cidx_all == cidx_all.max())
            last = np.where(
                amask[top],
                u32[np.minimum(offs[top] // 4 + ns[top] - 1, len(u32) - 1)],
                cbits - 1,
            )
            words = bp.row_words(int(cidx_all.max()) * cbits + int(last.max()))

        dense_rows = np.sort(uniq_rows[order[: self._dense_cap(words)]])
        slot_of = dict(zip(dense_rows.tolist(), range(len(dense_rows))))
        plane = bp.empty_plane(bp.pad_rows(len(dense_rows)), words)
        sparse: dict[int, np.ndarray] = {}

        # Per-container slot (-1 = sparse tier), via the uniq_rows table.
        slot_table = np.full(len(uniq_rows), -1, dtype=np.int64)
        slot_table[np.searchsorted(uniq_rows, dense_rows)] = np.arange(
            len(dense_rows)
        )
        cont_slots = (
            slot_table[np.searchsorted(uniq_rows, rows_of)]
            if n_cont
            else np.zeros(0, np.int64)
        )

        # Sparse rows holding any BITMAP container are rebuilt
        # per-row below (two payload forms must interleave in key
        # order); exclude them from the vectorized grouping.
        special_rows = (
            set(int(r) for r in rows_of[bmask & (cont_slots < 0)])
            if n_cont
            else set()
        )

        # ---- array containers: vectorized gather in bounded CHUNKS so
        # the transient index/value arrays never rival the tier itself
        # (an all-array 180 MB file would otherwise gather ~45M values
        # with int64 scratch — hundreds of MB of peak for nothing).
        _CHUNK_VALUES = self._LOAD_CHUNK_VALUES
        if n_cont and amask.any():
            a_idx = np.nonzero(amask)[0]
            csum = np.cumsum(ns[a_idx])
            special_arr = (
                np.asarray(sorted(special_rows)) if special_rows else None
            )
            sp_rows_parts: list[np.ndarray] = []
            sp_offs_parts: list[np.ndarray] = []
            start = 0
            while start < len(a_idx):
                floor = int(csum[start - 1]) if start else 0
                end = int(
                    np.searchsorted(csum, floor + _CHUNK_VALUES, side="right")
                )
                end = max(end, start + 1)
                blk = a_idx[start:end]
                ns_blk = ns[blk]
                offs32 = (offs[blk] // 4).astype(np.int64)
                total = int(ns_blk.sum())
                base_idx = np.repeat(
                    offs32 - np.insert(np.cumsum(ns_blk), 0, 0)[:-1], ns_blk
                )
                vals = u32[base_idx + np.arange(total)]
                del base_idx
                if total and int(vals.max()) >= cbits:
                    raise roaring.CorruptError("array value out of range")
                if total > 1:
                    d = np.diff(vals.astype(np.int64))
                    ok = d > 0
                    # container-boundary diffs are exempt (bnd-1 indexes
                    # d, and bnd <= total-1 always since every n >= 1);
                    # chunk edges are container boundaries too.
                    bnd = np.cumsum(ns_blk)[:-1]
                    ok[bnd - 1] = True
                    if not ok.all():
                        raise roaring.CorruptError(
                            "array container is not sorted/unique"
                        )
                    del d, ok
                # offsets within a slice fit int32 (< 2^20)
                cidx_rep = np.repeat(
                    (keys[blk] % cps).astype(np.int32), ns_blk
                )
                slots_rep = np.repeat(cont_slots[blk].astype(np.int32), ns_blk)
                off_in_slice = cidx_rep * np.int32(cbits) + vals.astype(
                    np.int32
                )
                del vals, cidx_rep

                dm = slots_rep >= 0
                if dm.any():
                    sel = off_in_slice[dm]
                    word = sel // np.int32(bp.WORD_BITS)
                    bits = (
                        np.uint32(1) << (sel % np.int32(bp.WORD_BITS)).astype(np.uint32)
                    ).astype(np.uint32)
                    np.bitwise_or.at(plane, (slots_rep[dm], word), bits)
                    del sel, word, bits
                sm = ~dm
                if sm.any():
                    rows_rep = np.repeat(rows_of[blk], ns_blk)
                    if special_arr is not None:
                        sm &= ~np.isin(rows_rep, special_arr)
                    if sm.any():
                        # boolean-mask indexing COPIES: compact buffers
                        # holding exactly the sparse values.
                        sp_rows_parts.append(rows_rep[sm])
                        sp_offs_parts.append(
                            off_in_slice[sm].astype(np.uint32)
                        )
                start = end
            if sp_rows_parts:
                # chunks ascend in container-key order, so the
                # concatenation is globally sorted by (row, offset);
                # per-row slices are views of ONE compact buffer.
                s_rows = np.concatenate(sp_rows_parts)
                s_offs = np.concatenate(sp_offs_parts)
                del sp_rows_parts, sp_offs_parts
                u_s, st = np.unique(s_rows, return_index=True)
                bounds = np.append(st, len(s_rows))
                for j, r in enumerate(u_s):
                    sparse[int(r)] = s_offs[bounds[j] : bounds[j + 1]]

        # ---- bitmap containers of dense rows: slice-assign payloads.
        if n_cont and bmask.any():
            for i in np.nonzero(bmask)[0]:
                slot = int(cont_slots[i])
                if slot < 0:
                    continue
                s32 = int(offs[i]) // 4
                cidx = int(keys[i]) % cps
                # wpc is u32 words per container (2048)
                plane[slot, cidx * wpc : (cidx + 1) * wpc] = u32[
                    s32 : s32 + wpc
                ]

        # ---- mixed-form sparse rows (rare): rebuild in key order.
        for r in sorted(special_rows):
            lo = int(np.searchsorted(rows_of, r, side="left"))
            hi = int(np.searchsorted(rows_of, r, side="right"))
            segs = []
            for i in range(lo, hi):
                cidx = int(keys[i]) % cps
                s32 = int(offs[i]) // 4
                if amask[i]:
                    vals_i = u32[s32 : s32 + int(ns[i])]
                else:
                    w = np.ascontiguousarray(
                        u32[s32 : s32 + wpc]
                    ).view(np.uint64)
                    vals_i = roaring.words_to_values(w)
                segs.append(
                    vals_i.astype(np.uint32) + np.uint32(cidx * cbits)
                )
            sparse[r] = (
                np.concatenate(segs) if segs else np.empty(0, np.uint32)
            )

        # ---- exact counts from the built tiers (payload-truthful,
        # like the replaced decode path's np_count sweep).  Row-block
        # sweeps keep the popcount temp out of the open peak.
        counts: dict[int, int] = {}
        if len(dense_rows):
            step = max(256, (1 << 22) // words)  # 16 MiB of plane a sweep
            cnts = np.concatenate(
                [
                    bp.np_row_counts(plane[b : b + step])
                    for b in range(0, len(dense_rows), step)
                ]
            )
            counts.update(zip(dense_rows.tolist(), cnts.tolist()))
        counts.update((r, len(offs_r)) for r, offs_r in sparse.items())

        # ---- op-log replay over the freshly-built tiers.
        op_n = 0
        max_row = int(uniq_rows.max()) if n_cont else 0
        for typ, value in roaring._iter_ops(mm, ops_offset):
            op_n += 1
            row, offset = divmod(value, SLICE_WIDTH)
            slot = slot_of.get(row)
            if slot is None and row not in sparse:
                if len(slot_of) < self._dense_cap(plane.shape[1]):
                    slot = slot_of[row] = len(slot_of)
                    if slot >= plane.shape[0]:
                        extra = np.zeros(
                            (bp.pad_rows(slot + 1) - plane.shape[0],
                             plane.shape[1]),
                            np.uint32,
                        )
                        plane = np.vstack([plane, extra])
                else:
                    sparse[row] = np.empty(0, np.uint32)
                counts.setdefault(row, 0)
            if slot is not None and offset >= plane.shape[1] * bp.WORD_BITS:
                if typ != roaring.OP_ADD:
                    continue  # nothing is set beyond the plane's columns
                # the replayed twin of _relayout_locked (less its
                # demotion: a row the log adds stays where it was put)
                wide = bp.empty_plane(plane.shape[0], bp.row_words(offset))
                wide[:, : plane.shape[1]] = plane
                plane = wide
            if slot is not None:
                if typ == roaring.OP_ADD:
                    changed = bp.np_set_bit(plane, slot * SLICE_WIDTH + offset)
                else:
                    changed = bp.np_clear_bit(plane, slot * SLICE_WIDTH + offset)
            else:
                offs_row = sparse[row]
                i = int(np.searchsorted(offs_row, offset))
                present = i < len(offs_row) and int(offs_row[i]) == offset
                if typ == roaring.OP_ADD and not present:
                    sparse[row] = np.insert(offs_row, i, np.uint32(offset))
                    changed = True
                elif typ == roaring.OP_REMOVE and present:
                    sparse[row] = np.delete(offs_row, i)
                    changed = True
                else:
                    changed = False
            if changed:
                counts[row] = counts.get(row, 0) + (
                    1 if typ == roaring.OP_ADD else -1
                )
                max_row = max(max_row, row)

        # ---- commit (everything above was local).
        self._slot_of = slot_of
        self._plane = plane
        self._sparse = sparse
        self._tier_arrays = None
        self._sparse_dev.clear()
        self._payload_cache.clear()
        self._sync_sparse_pool_locked()
        self._max_row_id = max_row
        self._count_of = counts
        self._block_sums.clear()
        self._dirty_blocks.clear()
        self._row_cache.clear()
        self._invalidate_device()
        _bump_write_epoch()
        return op_n

    def _load_tiered(
        self, words: dict[int, np.ndarray], arrays: dict[int, np.ndarray]
    ) -> None:
        """Replace storage from tiered containers (open/restore): the
        densest rows fill the dense tier first; the long sparse tail
        stays as offset arrays."""
        per_row: dict[int, list[tuple[int, np.ndarray, bool]]] = {}
        counts: dict[int, int] = {}
        for key, w in words.items():
            row, cidx = divmod(int(key), bp.CONTAINERS_PER_SLICE)
            per_row.setdefault(row, []).append((cidx, w, False))
            counts[row] = counts.get(row, 0) + bp.np_count(w)
        for key, vals in arrays.items():
            row, cidx = divmod(int(key), bp.CONTAINERS_PER_SLICE)
            per_row.setdefault(row, []).append((cidx, vals, True))
            counts[row] = counts.get(row, 0) + len(vals)

        by_density = sorted(per_row, key=lambda r: (-counts[r], r))
        cbits = roaring.CONTAINER_BITS
        words = bp.row_words(
            max(
                (
                    cidx * cbits
                    + (int(payload[-1]) if is_vals and len(payload) else cbits - 1)
                    for parts in per_row.values()
                    for cidx, payload, is_vals in parts
                ),
                default=0,
            )
        )
        cap = self._dense_cap(words)
        dense_rows = sorted(by_density[:cap])
        sparse_rows = by_density[cap:]

        self._slot_of = {r: i for i, r in enumerate(dense_rows)}
        plane = bp.empty_plane(bp.pad_rows(len(dense_rows)), words)
        wpc = bp.WORDS_PER_CONTAINER
        for i, r in enumerate(dense_rows):
            for cidx, payload, is_vals in per_row[r]:
                w = roaring.values_to_words(payload) if is_vals else payload
                w = w.view("<u4").astype(np.uint32)
                lo = cidx * wpc
                hi = min(lo + wpc, words)  # an array container may end early
                plane[i, lo:hi] = w[: hi - lo]
        self._plane = plane

        self._sparse = {}
        for r in sparse_rows:
            segs = []
            for cidx, payload, is_vals in sorted(per_row[r]):
                vals = payload if is_vals else roaring.words_to_values(payload)
                segs.append(
                    vals.astype(np.uint32) + np.uint32(cidx * roaring.CONTAINER_BITS)
                )
            self._sparse[r] = (
                np.concatenate(segs) if segs else np.empty(0, np.uint32)
            )
        self._sparse_dev.clear()
        self._payload_cache.clear()
        self._sync_sparse_pool_locked()

        self._max_row_id = max(per_row) if per_row else 0
        self._tier_arrays = None
        self._count_of = counts
        self._block_sums.clear()
        self._dirty_blocks.clear()
        self._invalidate_device()
        _bump_write_epoch()

    def _containers_packed(self) -> tuple[np.ndarray, np.ndarray, tuple]:
        """Current storage for serialization, with no Python step a row
        or a container in the dense tier: ``(keys u64 ascending,
        words2d u64[n, 1024], (akeys, acounts, avalues))`` for
        roaring.encode_packed — the containers held as bitmaps, and
        those held as value arrays (keys ascending, each container's
        count, every container's values one after another).  A plane
        whose rows span whole containers packs them as bitmaps; one
        whose rows are narrower than a container (a row is the head of
        its container 0) gives each row's values straight from its set
        words (``bp.np_plane_positions``): a million such rows as
        bitmaps would be 8 KiB each.  Sparse rows convert offsets ->
        values directly, never materializing a plane row."""
        wpc = bp.WORDS_PER_CONTAINER
        cps = bp.CONTAINERS_PER_SLICE
        cbits = roaring.CONTAINER_BITS
        width = int(self._plane.shape[1])
        ids, slots, _ = self._tier_key_arrays_locked()  # ids ascending
        n = len(ids)
        key_blocks: list[np.ndarray] = []
        payload_blocks: list[np.ndarray] = []
        akeys: list[np.ndarray] = []
        acounts: list[np.ndarray] = []
        avalues: list[np.ndarray] = []
        step = max(1, (32 << 20) // (width * 4))  # rows a sweep: 32 MiB
        for b in range(0, n, step):
            at = slots[b : b + step]
            # Bulk-import fragments allocate slots in id order, so the
            # common case is a contiguous ascending run — slice a VIEW
            # instead of gather-copying the block.
            if at[-1] - at[0] == len(at) - 1 and (np.diff(at) == 1).all():
                sub = self._plane[at[0] : at[-1] + 1]
            else:
                sub = self._plane[at]
            rows_arr = ids[b : b + step]
            if width < wpc:
                cnts = bp.np_row_counts(sub)
                small = (cnts > 0) & (cnts <= roaring.ARRAY_MAX_SIZE)
                if small.any():
                    akeys.append((rows_arr[small] * cps).astype(np.uint64))
                    acounts.append(cnts[small])
                    avalues.append(
                        bp.np_plane_positions(sub if small.all() else sub[small])
                    )
                big = cnts > roaring.ARRAY_MAX_SIZE
                if big.any():
                    padded = np.zeros((int(big.sum()), wpc), np.uint32)
                    padded[:, :width] = sub[big]
                    key_blocks.append((rows_arr[big] * cps).astype(np.uint64))
                    payload_blocks.append(padded)
                continue
            held = width // wpc  # whole containers a plane row spans
            sub = np.ascontiguousarray(sub).reshape(len(at), held, wpc)
            # Nonzero test on the u64 view: half the elements.
            nonzero = sub.view(np.uint64).any(axis=2)
            if not nonzero.any():
                continue
            key_blocks.append(
                (rows_arr[:, None] * cps + np.arange(held)[None, :])[
                    nonzero
                ].astype(np.uint64)
            )
            payload_blocks.append(sub[nonzero])
        if key_blocks:
            keys = np.concatenate(key_blocks)
            words2d = (
                np.ascontiguousarray(np.concatenate(payload_blocks))
                .view(np.uint64)
                .reshape(len(keys), wpc // 2)
            )
        else:
            keys = np.zeros(0, np.uint64)
            words2d = np.zeros((0, wpc // 2), np.uint64)
        # Sparse tier, vectorized across ALL rows at once: rows visit in
        # ascending order and offsets ascend within a row, so the global
        # key stream is non-decreasing — one unique() groups it.
        sp_rows = sorted(r for r in self._sparse if len(self._sparse[r]))
        if sp_rows:
            lens = np.asarray([len(self._sparse[r]) for r in sp_rows])
            rows_rep = np.repeat(np.asarray(sp_rows, dtype=np.int64), lens)
            offs_all = np.concatenate([self._sparse[r] for r in sp_rows])
            keys_all = rows_rep * cps + offs_all // cbits
            uniq_keys, starts = np.unique(keys_all, return_index=True)
            akeys.append(uniq_keys.astype(np.uint64))
            acounts.append(np.diff(np.append(starts, len(keys_all))))
            avalues.append((offs_all % cbits).astype(np.uint32))
        if not akeys:
            return keys, words2d, ()
        return keys, words2d, roaring.merge_packed_arrays(
            np.concatenate(akeys), np.concatenate(acounts), np.concatenate(avalues)
        )

    def _row_words_host(self, row_id: int) -> np.ndarray | None:
        """One row's words on host (copy), whichever tier holds it.
        Takes the fragment lock itself (reentrant) — callers like the
        executor's host batch assembly read concurrently with writers
        that replace the plane or migrate rows between tiers."""
        with self._mu:
            slot = self._slot_of.get(row_id)
            if slot is not None:
                return bp.widen_row(self._plane[slot])
            offs = self._sparse.get(row_id)
            if offs is None:
                return None
            return bp.np_columns_to_row(offs)

    # ------------------------------------------------------------------
    # reads
    # ------------------------------------------------------------------

    def row(self, row_id: int) -> RowBitmap:
        """Extract one row as a RowBitmap segment (reference:
        fragment.go:340-375 row via roaring.OffsetRange).

        Only dense-tier rows are cached: caching a materialized sparse
        row would cost 128 KiB per entry in an unbounded dict —
        reintroducing the rows x 128 KiB footprint the sparse tier
        removes."""
        with self._mu:
            seg = self._row_cache.get(row_id)
            if seg is None:
                seg = self._row_words_host(row_id)
                if seg is None:
                    seg = bp.empty_row()
                if row_id not in self._sparse:
                    self._row_cache[row_id] = seg
            return RowBitmap.from_segment(self.slice, seg.copy())

    def contains(self, row_id: int, column_id: int) -> bool:
        with self._mu:
            offset = self.pos(row_id, column_id) % SLICE_WIDTH
            slot = self._slot_of.get(row_id)
            if slot is not None:
                if offset >= self._plane.shape[1] * bp.WORD_BITS:
                    return False
                return bp.np_contains(self._plane, slot * SLICE_WIDTH + offset)
            offs = self._sparse.get(row_id)
            if offs is None:
                return False
            i = int(np.searchsorted(offs, offset))
            return i < len(offs) and int(offs[i]) == offset

    def count(self) -> int:
        """Total set bits — from the incrementally-maintained per-row
        counts: no plane scan and no device round-trip (the counts are
        exact under set/clear/import, like the reference's cached
        bitmap.n bookkeeping, bitmap.go:184-217)."""
        with self._mu:
            return sum(self._count_of.values())

    def row_counts(self) -> dict[int, int]:
        """{row_id: popcount} for every touched row (host-side, O(rows))."""
        with self._mu:
            return dict(self._count_of)

    # Above this many queued point writes, a full re-upload is cheaper
    # than the scatter program.
    _MAX_DEVICE_PENDING = 8192

    # Array-container values gathered per sweep in _load_direct (~1M
    # values -> ~25 MB scratch); tests shrink it to force multi-chunk
    # loads on small fixtures.
    _LOAD_CHUNK_VALUES = 1 << 20

    def _invalidate_device(self) -> None:
        """Bulk plane changes (import, restore, load) force a full
        re-upload; queued point updates would be stale.  The residency
        pool drops the mirror's accounting with it."""
        self._device = None
        self._device_version = -1
        self._device_pending.clear()
        self._pending_slots.clear()
        device_mod.pool().remove(self._pool_key)

    def _pool_info(self) -> dict:
        return {
            "fragment": f"{self.index}/{self.frame}/{self.view}/{self.slice}",
            "slice": self.slice,
        }

    def _evict_mirror(self) -> bool:
        """Residency-pool eviction hook: drop the HBM mirror.  The host
        plane is authoritative, so the next ``device_plane()`` rebuilds
        it — but ``_device_pending`` must clear COHERENTLY under the
        fragment lock: queued point writes describe deltas against the
        dropped mirror, and replaying them onto a freshly-uploaded
        (already current) plane would be wrong.  Non-blocking acquire:
        the pool may pick this fragment while another thread is inside
        ``device_plane()``; skipping an actively-used mirror is always
        safe, dropping it mid-upload is not."""
        if not self._mu.acquire(blocking=False):
            return False
        try:
            self._device = None
            self._device_version = -1
            self._device_pending.clear()
            self._pending_slots.clear()
            return True
        finally:
            self._mu.release()

    def _evict_sparse_rows(self) -> bool:
        """Residency-pool eviction hook for the paged-sparse-row cache:
        page everything out (rebuilt on demand from the host offset
        arrays)."""
        if not self._mu.acquire(blocking=False):
            return False
        try:
            self._sparse_dev.clear()
            self._sparse_dev_nbytes = 0
            return True
        finally:
            self._mu.release()

    def _sync_sparse_pool_locked(self) -> None:
        """Re-account the paged-sparse-row cache after it changed
        (page-in, write invalidation, promotion, bulk load).  Resident
        bytes are the COMPRESSED payload sizes; the pool entry's info
        carries the logical dense equivalent (rows x 128 KiB) and the
        container-format mix so /debug/hbm can report compressed vs
        logical.  Callers hold ``_mu``."""
        ents = self._sparse_dev.values()
        self._sparse_dev_nbytes = sum(e[2] for e in ents)
        n = len(self._sparse_dev)
        if n == 0:
            device_mod.pool().remove(self._sparse_pool_key)
        else:
            mix: dict[str, int] = {}
            for fmt, _dev, _nb in ents:
                name = bp.FMT_NAMES.get(fmt, str(fmt))
                mix[name] = mix.get(name, 0) + 1
            info = dict(self._pool_info())
            info["logical_bytes"] = n * ROW_NBYTES
            info["formats"] = mix
            device_mod.pool().resize(
                self._sparse_pool_key,
                {bp.home_device(self.slice): self._sparse_dev_nbytes},
                info=info,
            )

    @property
    def plane_nbytes(self) -> int:
        """Host dense-plane byte size — what a staged device mirror
        costs in HBM (pad_rows keeps the plane in pow2 row classes, so
        this is also the mirror's compile-shape bucket x 128 KiB).
        The staging/warming paths order and account by it."""
        return int(self._plane.nbytes)

    def device_plane(self):
        """The HBM mirror of the plane, pinned to the slice's home device
        (slice mod n_devices) so multi-device query batches assemble
        shard-local (parallel/mesh.home_device).  Point writes since the
        last read apply as one batched on-device scatter; bulk changes
        re-upload.  Every (re)upload admits through the residency pool
        FIRST, so LRU mirrors are evicted to make room and accounted
        residency never exceeds the HBM budget."""
        import jax

        with self._mu:
            pool = device_mod.pool()
            if self._device is not None and self._device_version != self._version:
                if self._device_pending:
                    # Incremental mirror maintenance: ONE fused scatter
                    # launch applies the queued deltas (ingest/scatter:
                    # pow2-bucketed update axis, no donation).  The pin
                    # lease keeps the pool from evicting the mirror
                    # between gather and scatter; publication is the
                    # plain attribute swap below, so a concurrent
                    # reader holding the OLD array sees a consistent
                    # (old) plane — version-fenced atomicity.
                    with pool.pinned(self._pool_key):
                        self._device = ingest_scatter.apply(
                            self._device, self._device_pending
                        )
                    self._device_pending.clear()
                    self._pending_slots.clear()
                    self._device_version = self._version
                else:
                    self._device = None
            if self._device is None or self._device_version != self._version:
                dev = bp.home_device(self.slice)
                pool.admit(
                    self._pool_key,
                    {dev: int(self._plane.nbytes)},
                    self._evict_mirror,
                    category="mirror",
                    info=self._pool_info(),
                )
                try:
                    self._device = jax.device_put(self._plane, dev)
                except BaseException:
                    pool.remove(self._pool_key)
                    raise
                pool.count_restage(int(self._plane.nbytes))
                self._device_pending.clear()
                self._pending_slots.clear()
                self._device_version = self._version
            else:
                pool.touch(self._pool_key)
            return self._device

    def _mirror_locked(self):
        """``device_plane()`` for a reader of MANY fragments' mirrors,
        which keeps them recent in the residency pool itself, all under
        one hold of the pool's lock (``PlanePool.touch_many``): a mirror
        that is resident and current is handed over with no pool call.
        The pool has ONE lock; a touch a fragment from every request
        thread is a convoy on it (eight TopN builds over 954 fragments
        each took 40 times what one takes alone, PERF.md PR 35).
        Anything else — an upload, a queued scatter — goes through
        ``device_plane()``.  Callers hold ``_mu``."""
        if self._device is not None and self._device_version == self._version:
            return self._device
        return self.device_plane()

    def plane_rows(self) -> int:
        """Rows of the dense plane as allocated (a pow2 class, floor
        ROW_BLOCK): the row dimension of every program that reads the
        plane's device mirror."""
        return int(self._plane.shape[0])

    def plane_words(self) -> int:
        """Words of a plane row (``bp.row_words`` of the highest column
        the fragment holds): the word dimension of the same programs."""
        return int(self._plane.shape[1])

    def mirror_is(self, plane) -> bool:
        """Whether ``plane`` is this fragment's CURRENT device mirror,
        the array the residency pool accounts for under the fragment's
        own key (a snapshot taken before a later write's refresh, or
        before an eviction, is not).  One attribute read, no lock."""
        return self._device is plane

    def has_row(self, row_id: int) -> bool:
        """Whether either tier holds the row (no device work)."""
        with self._mu:
            return row_id in self._slot_of or row_id in self._sparse

    def _slots_locked(self, row_ids) -> list | None:
        """The plane slot of each row, -1 for a row the fragment does
        not hold; None when a row lives in the sparse tier."""
        slots = []
        for row_id in row_ids:
            slot = self._slot_of.get(row_id)
            if slot is None:
                if row_id in self._sparse:
                    return None
                slot = -1
            slots.append(slot)
        return slots

    def slots_of(self, row_ids) -> tuple | None:
        """``([slot, ...], version)``: where each row lies in the plane
        (-1: not held) and the version that holds for, with no device
        work — a layout to keep while the version stands.  None when a
        row lives in the sparse tier: no plane holds it."""
        with self._mu:
            slots = self._slots_locked(row_ids)
            return None if slots is None else (slots, self._version)

    def fresh_mirror(self, version: int):
        """The device mirror if it is resident and current at
        ``version``, else None.  No upload, no pool touch: a reader that
        kept slots from ``slots_of`` at that version asks this first,
        and takes ``gather_slots`` (an upload or a scatter on the way)
        only when it says None.  The mirror and the version it holds
        for are read under the lock that publishes them, so an
        acknowledged write is never answered from the array before it.
        The mirror is an immutable snapshot: a later write publishes a
        new array."""
        with self._mu:
            if self._version != version or self._device_version != version:
                return None
            return self._device

    def gather_slots(self, row_ids) -> tuple | None:
        """``(mirror snapshot, [slot, ...])`` for the device gather of a
        leaf batch (exec/executor.py, ``bp.gather_planes``): the slot of
        each row in the plane, -1 for a row the fragment does not hold,
        and the mirror those slots index, both read under one hold of
        the lock (``device_plane()`` applies a queued point-write scatter
        first, so an acknowledged write is in the snapshot).  The mirror
        is None, and nothing is uploaded, when no row is held.  None when
        a row lives in the sparse tier: no plane holds it."""
        with self._mu:
            slots = self._slots_locked(row_ids)
            if slots is None:
                return None
            if max(slots, default=-1) < 0:
                return None, slots
            return self.device_plane(), slots

    def device_row(self, row_id: int):
        """One row as a device leaf for query plans (exec/plan.py).

        Dense rows gather from the HBM plane mirror (no host copy);
        sparse rows PAGE on demand — materialized host-side and
        device_put to the slice's home device, kept in a small LRU so
        repeated queries over the same sparse rows (e.g. inverse-view
        Bitmap calls) hit HBM (SURVEY.md §7 "row-block paging HBM<->host
        for sparse-tall frames")."""
        import jax

        with self._mu:
            slot = self._slot_of.get(row_id)
            if slot is not None:
                dev = self._device
                if (
                    dev is not None
                    and self._device_version != self._version
                    and slot not in self._pending_slots
                ):
                    # Row-level freshness: the mirror is stale only
                    # where queued deltas touch, and this row isn't
                    # among them (a change the queue can't express
                    # drops the mirror entirely), so the resident
                    # plane's row is byte-exact as-is.  Serving it
                    # directly keeps an ingest storm on OTHER rows from
                    # forcing a whole-plane sync onto every read.
                    device_mod.pool().touch(self._pool_key)
                    return self._wide_device_row(dev[slot])
                return self._wide_device_row(self.device_plane()[slot])
            ent = self._sparse_dev_entry_locked(row_id)
            if ent is None:
                return None
            fmt, dev, _nb = ent
            # Transient dense expansion for the stacking caller; the
            # resident cache keeps only the compressed payload, so HBM
            # never holds a decompressed staging copy.
            return bp.expand_payload(fmt, dev)

    @staticmethod
    def _wide_device_row(row):
        """A row of the mirror as the full-width leaf a query plan
        takes: a narrow plane's row is padded with zeros on read."""
        import jax.numpy as jnp

        pad = bp.WORDS_PER_SLICE - int(row.shape[0])
        return jnp.pad(row, (0, pad)) if pad else row

    def _sparse_dev_entry_locked(self, row_id: int):
        """The paged compressed-container entry ``(fmt, device_payload,
        encoded_nbytes)`` for a sparse-tier row, paging it in (pool
        admission first, at COMPRESSED bytes) on miss.  Callers hold
        ``_mu``; returns None when the row is absent."""
        import jax

        offs = self._sparse.get(row_id)
        if offs is None:
            return None
        ent = self._sparse_dev.get(row_id)
        if ent is not None:
            self._sparse_dev.move_to_end(row_id)
            device_mod.pool().touch(self._sparse_pool_key)
            return ent
        fmt, payload, nbytes = self._host_payload_locked(row_id, offs)
        home = bp.home_device(self.slice)
        device_mod.pool().admit(
            self._sparse_pool_key,
            {home: self._sparse_dev_nbytes + nbytes},
            self._evict_sparse_rows,
            category="sparse",
            info=self._pool_info(),
        )
        dev = jax.device_put(payload, home)
        ent = self._sparse_dev[row_id] = (fmt, dev, nbytes)
        while len(self._sparse_dev) > SPARSE_DEVICE_CACHE:
            self._sparse_dev.popitem(last=False)
        self._sync_sparse_pool_locked()
        return ent

    def _host_payload_locked(self, row_id: int, offs) -> tuple:
        """Write-time-selected container encoding of one sparse-tier
        row — ``(fmt, payload, encoded_nbytes)``, memoized until the
        row mutates (_after_write pops it, which is also how a write
        triggers format RE-selection: the next encode sees the new
        density)."""
        ent = self._payload_cache.get(row_id)
        if ent is None:
            ent = self._payload_cache[row_id] = bp.encode_row(offs)
        return ent

    def host_payload(self, row_id: int):
        """Host-side container view of any present row: ``(fmt,
        payload, encoded_nbytes, cardinality)``.  Dense-tier rows are
        FMT_DENSE views of the authoritative plane (callers copy into
        batches, never mutate); sparse-tier rows return the memoized
        compressed encoding.  None when the row is absent — the
        executor's anchored count assembles its format-dispatched leaf
        batches from this."""
        with self._mu:
            slot = self._slot_of.get(row_id)
            if slot is not None:
                row = self._plane[slot]
                return (
                    bp.FMT_DENSE,
                    row if len(row) == bp.WORDS_PER_SLICE else bp.widen_row(row),
                    ROW_NBYTES,
                    self._count_of.get(row_id, 0),
                )
            offs = self._sparse.get(row_id)
            if offs is None:
                return None
            fmt, payload, nbytes = self._host_payload_locked(row_id, offs)
            return (fmt, payload, nbytes, len(offs))

    def holds_sparse_tier_rows(self) -> bool:
        """Whether any row lives in the sparse tier.  While none does,
        every present row is FMT_DENSE by placement (View.dense_tier_only
        sums this over a view)."""
        with self._mu:
            return bool(self._sparse)

    def row_meta(self, row_id: int) -> tuple:
        """``(cardinality, fmt)`` of one row — what ``host_payload``
        reports as its last and first fields, without building the
        payload or touching a plane (the cardinality is the cached
        popcount); ``(0, None)`` when the row is absent.  A row with a
        dense-tier slot is FMT_DENSE by placement; a sparse-tier row
        answers from its memoized encoding (O(cardinality) once per
        mutation).  The anchored count's metadata walk decides its
        route from this alone."""
        with self._mu:
            if row_id in self._slot_of:
                return self._count_of.get(row_id, 0), bp.FMT_DENSE
            offs = self._sparse.get(row_id)
            if offs is None:
                return 0, None
            fmt = self._host_payload_locked(row_id, offs)[0]
            return self._count_of.get(row_id, 0), fmt

    def row_positions(self, row_id: int):
        """Sorted uint32 in-slice positions of one present row (the
        anchored count's anchor vector), or None.  O(cardinality) for
        sparse-tier rows; dense-tier rows pay one 128 KiB plane-row
        scan (unpacked to 1 MiB of bytes), so the anchored count calls
        this only in its second pass, once row_meta has said the route
        answers — never before a decline."""
        with self._mu:
            slot = self._slot_of.get(row_id)
            if slot is not None:
                return bp.np_row_to_columns(self._plane[slot]).astype(
                    np.uint32
                )
            offs = self._sparse.get(row_id)
            if offs is None:
                return None
            return np.asarray(offs, dtype=np.uint32)

    # ------------------------------------------------------------------
    # writes (reference: fragment.go:379-473)
    # ------------------------------------------------------------------

    def set_bit(self, row_id: int, column_id: int) -> bool:
        with self._mu:
            self._check_writable_locked()
            pos = self.pos(row_id, column_id)
            offset = pos % SLICE_WIDTH
            grew = row_id > self._max_row_id
            slot = self._ensure_slot(row_id)
            if slot is not None:
                self._ensure_width_locked(offset)
                # a re-lay may have moved the row to the sparse tier
                slot = self._slot_of.get(row_id)
            if slot is not None:
                changed = bp.np_set_bit(self._plane, slot * SLICE_WIDTH + offset)
                if changed:
                    self._queue_device_update(slot, offset, 1)
            else:
                changed = self._sparse_insert(row_id, offset)
            if changed:
                self._append_op(roaring.OP_ADD, pos)
                self._after_write(row_id, +1)
                self.stats.count("setBit")  # reference: fragment.go:418
                if grew:
                    # reference: fragment.go:421-423
                    self.stats.gauge("rows", float(self._max_row_id))
                self._maybe_promote(row_id)
                if _write_listeners or self._frag_write_listeners:
                    _notify_write(
                        self, (row_id,), (column_id,), (), (), exact=True
                    )
            return changed

    def clear_bit(self, row_id: int, column_id: int) -> bool:
        with self._mu:
            self._check_writable_locked()
            pos = self.pos(row_id, column_id)
            offset = pos % SLICE_WIDTH
            slot = self._slot_of.get(row_id)
            if slot is not None:
                changed = offset < self._plane.shape[
                    1
                ] * bp.WORD_BITS and bp.np_clear_bit(
                    self._plane, slot * SLICE_WIDTH + offset
                )
                if changed:
                    self._queue_device_update(slot, offset, 0)
            elif row_id in self._sparse:
                changed = self._sparse_remove(row_id, offset)
            else:
                return False
            if changed:
                self._append_op(roaring.OP_REMOVE, pos)
                self._after_write(row_id, -1)
                self.stats.count("clearBit")  # reference: fragment.go:470
                if _write_listeners or self._frag_write_listeners:
                    _notify_write(
                        self, (), (), (row_id,), (column_id,), exact=True
                    )
            return changed

    def _sparse_insert(self, row_id: int, offset: int) -> bool:
        offs = self._sparse[row_id]
        i = int(np.searchsorted(offs, offset))
        if i < len(offs) and int(offs[i]) == offset:
            return False
        self._sparse[row_id] = np.insert(offs, i, np.uint32(offset))
        return True

    def _sparse_remove(self, row_id: int, offset: int) -> bool:
        offs = self._sparse[row_id]
        i = int(np.searchsorted(offs, offset))
        if i >= len(offs) or int(offs[i]) != offset:
            return False
        self._sparse[row_id] = np.delete(offs, i)
        return True

    def _queue_device_update(self, slot: int, offset: int, op: int) -> None:
        """Record a point write for the device mirror; overflow (or
        scatter disabled by config) degrades to a full re-upload on
        next read."""
        if self._device is None:
            return
        if not ingest_scatter.ENABLED:
            # Historical behavior: every point write invalidates the
            # mirror (and the next read re-stages the whole plane) —
            # kept as the config-off arm and the bench contrast.
            ingest_scatter.note_fallback()
            self._invalidate_device()
            return
        if len(self._device_pending) >= self._MAX_DEVICE_PENDING:
            ingest_scatter.note_fallback()
            self._invalidate_device()
            return
        word, shift = divmod(offset, bp.WORD_BITS)
        self._device_pending.append((slot, word, 1 << shift, op))
        self._pending_slots.add(slot)

    def apply_pending_scatter(self) -> bool:
        """Fold queued point-write deltas into the device mirror NOW
        (one fused scatter launch) instead of at the next read.  The
        ingest committer calls this on its group-commit tick, so a read
        storm usually finds the mirror already clean and pays nothing.
        No-op unless a mirror is resident with queued deltas; returns
        True when a launch was dispatched."""
        with self._mu:
            if (
                self._device is None
                or self._device_version == self._version
                or not self._device_pending
            ):
                return False
            pool = device_mod.pool()
            with pool.pinned(self._pool_key):
                self._device = ingest_scatter.apply(
                    self._device, self._device_pending
                )
            self._device_pending.clear()
            self._pending_slots.clear()
            self._device_version = self._version
            pool.touch(self._pool_key)
            return True

    def _queue_import_updates_locked(
        self, set_slots, set_offs, clr_slots, clr_offs
    ) -> None:
        """Queue a bulk import's dense-plane bits as scatter deltas when
        the import is small enough; otherwise fall back to full mirror
        invalidation (one re-upload beats thousands of folded updates,
        and sparse-tier bits never touch the mirror anyway)."""
        n = (0 if set_slots is None else len(set_slots)) + (
            0 if clr_slots is None else len(clr_slots)
        )
        if (
            self._device is None
            or not ingest_scatter.ENABLED
            or n == 0
            or n > ingest_scatter.IMPORT_SCATTER_MAX
            or len(self._device_pending) + n > self._MAX_DEVICE_PENDING
        ):
            if self._device is not None:
                ingest_scatter.note_fallback()
            self._invalidate_device()
            return
        for slots, offs_a, op in (
            (set_slots, set_offs, 1),
            (clr_slots, clr_offs, 0),
        ):
            if slots is None:
                continue
            words, shifts = np.divmod(
                np.asarray(offs_a, dtype=np.int64), bp.WORD_BITS
            )
            for slot, word, shift in zip(slots, words, shifts):
                self._device_pending.append(
                    (int(slot), int(word), 1 << int(shift), op)
                )
                self._pending_slots.add(int(slot))

    def _after_write(self, row_id: int, delta: int) -> None:
        self._version += 1
        _bump_write_epoch()
        self._row_cache.pop(row_id, None)
        # Dropping the encoded payload IS the format re-selection hook:
        # the next read re-encodes at the row's new density (a sparse
        # row crossing a threshold lands in a different container).
        self._payload_cache.pop(row_id, None)
        if self._sparse_dev.pop(row_id, None) is not None:
            self._sync_sparse_pool_locked()
        self._dirty_blocks.add(row_id // HASH_BLOCK_SIZE)
        n = self._count_of[row_id] = self._count_of.get(row_id, 0) + delta
        self.cache.add(row_id, n)
        self._op_n += 1
        if self._op_n >= self.max_op_n and not self._wal_replaying:
            # Mid-replay snapshots would truncate the WAL segment being
            # replayed; recovery checkpoints once, after the replay.
            self.snapshot()

    # Flush the op buffer once it holds this many bytes (~5k ops) even
    # between boundaries, bounding worst-case loss and memory.
    _OP_FLUSH_BYTES = roaring.OP_FLUSH_BYTES

    def _append_op(self, typ: int, pos: int) -> None:
        if self._file is not None:
            self._op_buf += roaring.encode_op(typ, pos)
            if len(self._op_buf) >= self._OP_FLUSH_BYTES:
                self._flush_ops_locked()
        if self._wal is not None and not self._wal_replaying:
            # Log-before-ack: the same changed-op record goes to the
            # WAL; the ack path waits on its group-commit fsync
            # (executor wait_durable).  During recovery replay the op
            # is already IN the WAL.  A shutdown race (writer closed
            # under us) degrades to the historical op-buf durability.
            try:
                self._wal.log(typ, pos)
            except ingest_wal.WalClosed:
                pass

    def _flush_ops_locked(self) -> None:
        if self._op_buf and self._file is not None:
            self._file.seek(0, os.SEEK_END)
            self._file.write(self._op_buf)
            self._file.flush()
        self._op_buf.clear()

    def flush_ops(self) -> None:
        """Group-commit boundary: persist buffered op-log records."""
        with self._mu:
            self._flush_ops_locked()

    def _slot_table_locked(self, uniq: np.ndarray):
        """``(slots, absent)`` of the sorted distinct row ids ``uniq``:
        each row's dense-tier slot, -1 for a sparse-tier row and for a
        row the fragment does not hold, and which those last are — from
        the tiers' sorted key arrays, no dict probe a row.  Callers
        hold ``_mu``."""
        slot_ids, slot_vals, sparse_ids = self._tier_key_arrays_locked()
        slots = np.full(len(uniq), -1, dtype=np.int64)
        absent = np.ones(len(uniq), dtype=bool)
        if len(slot_ids) and len(uniq):
            at = np.minimum(np.searchsorted(slot_ids, uniq), len(slot_ids) - 1)
            hit = slot_ids[at] == uniq
            slots[hit] = slot_vals[at[hit]]
            absent &= ~hit
        if len(sparse_ids) and len(uniq):
            at = np.minimum(np.searchsorted(sparse_ids, uniq), len(sparse_ids) - 1)
            absent &= sparse_ids[at] != uniq
        return slots, absent

    def import_bulk(
        self,
        row_ids: Sequence[int],
        column_ids: Sequence[int],
        clear_row_ids: Sequence[int] | None = None,
        clear_column_ids: Sequence[int] | None = None,
    ) -> None:
        """Bulk load: op-log off, vectorized scatter, cache recount per
        touched row, snapshot (reference: fragment.go:936-1004).

        ``clear_row_ids``/``clear_column_ids`` optionally clear bits in
        the same pass (one snapshot, one recount) — the overwrite half
        of a BSI value import.  Clears never create rows; a clear on an
        absent row is a no-op.  A bit must not appear in both lists."""
        clear_row_ids = clear_row_ids if clear_row_ids is not None else []
        clear_column_ids = (
            clear_column_ids if clear_column_ids is not None else []
        )
        if len(row_ids) != len(column_ids) or len(clear_row_ids) != len(
            clear_column_ids
        ):
            raise FragmentError("mismatch of row/column len")
        if len(row_ids) == 0 and len(clear_row_ids) == 0:
            return
        with self._mu:
            self._check_writable_locked()
            rows = np.asarray(row_ids, dtype=np.int64)
            cols = np.asarray(column_ids, dtype=np.int64)
            min_col = self.slice * SLICE_WIDTH
            if ((cols < min_col) | (cols >= min_col + SLICE_WIDTH)).any():
                raise FragmentError("column out of bounds for slice")
            offs = cols % SLICE_WIDTH
            uniq = np.unique(rows)
            # Bit positions are u64 in the op-log (pos = row*2^20 +
            # offset): reject before mutating state (_ensure_slot).
            if len(uniq) and not 0 <= int(uniq[0]) <= int(uniq[-1]) < MAX_ROW_ID:
                raise FragmentError(f"row id out of range: {int(uniq[-1])}")
            if len(offs):
                self._ensure_width_locked(int(offs.max()))
            # Every row's slot through tables over the UNIQUE rows, and
            # no Python step a row for a row the dense tier takes: a
            # unit of a tall frame brings hundreds of thousands of new
            # ones.  (-1: the sparse tier.)
            slot_table, new = self._slot_table_locked(uniq)
            fresh = uniq[new]
            if len(fresh):
                room = max(self._dense_cap() - len(self._slot_of), 0)
                dense, sparse = fresh[:room], fresh[room:]
                if len(dense):
                    base = len(self._slot_of)
                    # one allocation, not O(log n) doubling copies
                    self._reserve_dense(base + len(dense))
                    at = np.arange(base, base + len(dense))
                    self._slot_of.update(zip(dense.tolist(), at.tolist()))
                    slot_table[np.flatnonzero(new)[:room]] = at
                for r in sparse.tolist():
                    self._sparse[r] = np.empty(0, dtype=np.uint32)
                self._max_row_id = max(self._max_row_id, int(fresh[-1]))
                self._tier_arrays = None  # the tiers hold new rows
            slots_all = slot_table[np.searchsorted(uniq, rows)]
            dense_mask = slots_all >= 0
            imp_set_slots = imp_set_offs = None
            imp_clr_slots = imp_clr_offs = None
            if dense_mask.any():
                imp_set_slots = slots_all[dense_mask]
                imp_set_offs = offs[dense_mask]
                bp.np_set_bulk(self._plane, imp_set_slots, imp_set_offs)
            if not dense_mask.all():
                s_rows = rows[~dense_mask]
                s_offs = offs[~dense_mask].astype(np.uint32)
                order = np.lexsort((s_offs, s_rows))
                s_rows, s_offs = s_rows[order], s_offs[order]
                uniq_s = np.unique(s_rows)
                starts = np.searchsorted(s_rows, uniq_s)
                for i, r in enumerate(uniq_s):
                    hi = starts[i + 1] if i + 1 < len(starts) else len(s_rows)
                    seg = s_offs[starts[i] : hi]
                    cur = self._sparse[int(r)]
                    if len(cur) == 0:
                        # brand-new row (the tall-import common case):
                        # the sorted segment IS the row, minus dups
                        merged = seg[
                            np.insert(np.diff(seg) != 0, 0, True)
                        ] if len(seg) > 1 else seg
                    else:
                        merged = np.union1d(cur, seg).astype(np.uint32)
                    self._sparse[int(r)] = merged

            # ---- clears (the BSI overwrite path): clears only touch
            # rows that EXIST; dense rows take one vectorized andnot
            # scatter, sparse rows a per-row sorted difference.
            if len(clear_row_ids):
                c_rows = np.asarray(clear_row_ids, dtype=np.int64)
                c_cols = np.asarray(clear_column_ids, dtype=np.int64)
                if ((c_cols < min_col) | (c_cols >= min_col + SLICE_WIDTH)).any():
                    raise FragmentError("column out of bounds for slice")
                c_offs = c_cols % SLICE_WIDTH
                c_uniq = np.unique(c_rows)
                c_table, absent = self._slot_table_locked(c_uniq)
                c_slots = c_table[np.searchsorted(c_uniq, c_rows)]
                # clears never create rows, and nothing is set beyond
                # the columns the plane covers
                c_keep = ~absent[np.searchsorted(c_uniq, c_rows)] & (
                    (c_slots < 0) | (c_offs < self._plane.shape[1] * bp.WORD_BITS)
                )
                c_rows, c_offs, c_slots = c_rows[c_keep], c_offs[c_keep], c_slots[c_keep]
                dm = c_slots >= 0
                if dm.any():
                    imp_clr_slots = c_slots[dm]
                    imp_clr_offs = c_offs[dm]
                    bp.np_clear_bulk(self._plane, imp_clr_slots, imp_clr_offs)
                if (~dm).any():
                    s_rows = c_rows[~dm]
                    s_offs = c_offs[~dm].astype(np.uint32)
                    for r in np.unique(s_rows):
                        self._sparse[int(r)] = np.setdiff1d(
                            self._sparse[int(r)], s_offs[s_rows == r]
                        ).astype(np.uint32)
                uniq = np.union1d(uniq, c_uniq[~absent]).astype(np.int64)
                slot_table = self._slot_table_locked(uniq)[0]

            self._version += 1
            _bump_write_epoch()
            self._queue_import_updates_locked(
                imp_set_slots, imp_set_offs, imp_clr_slots, imp_clr_offs
            )
            self._sparse_dev.clear()
            self._payload_cache.clear()
            self._sync_sparse_pool_locked()
            self._row_cache.clear()
            self._dirty_blocks.update(np.unique(uniq // HASH_BLOCK_SIZE).tolist())
            # Recount the touched rows: the dense ones from the plane, a
            # sweep of bounded size at a time.
            in_plane = slot_table >= 0
            d_ids, d_slots = uniq[in_plane], slot_table[in_plane]
            if len(d_ids):
                step = max(256, (1 << 22) // self._plane.shape[1])
                cnts = np.concatenate(
                    [
                        bp.np_row_counts(self._plane[d_slots[b : b + step]])
                        for b in range(0, len(d_slots), step)
                    ]
                )
                self._count_of.update(zip(d_ids.tolist(), cnts.tolist()))
                self.cache.bulk_add_many(d_ids, cnts)
            for r in uniq[~in_plane].tolist():
                n = len(self._sparse[r])
                self._count_of[r] = n
                self.cache.bulk_add(r, n)
                self._maybe_promote(r)
            self.cache.invalidate()
            self.cache.recalculate()
            self.stats.count("ImportBit", len(row_ids))  # ref: fragment.go:969
            if _write_listeners or self._frag_write_listeners:
                _notify_write(
                    self, row_ids, column_ids, clear_row_ids, clear_column_ids
                )
            self.snapshot()

    def snapshot(self) -> None:
        """Full roaring serialization atomically renamed over the data
        file; resets the op count (reference: fragment.go:1032-1074)."""
        with self._mu:
            t0 = time.perf_counter()
            # Buffered ops are subsumed by the serialized state below.
            self._op_buf.clear()
            data = roaring.encode_packed(*self._containers_packed())
            tmp = self.path + ".snapshotting"
            with open(tmp, "wb") as fh:
                fh.write(data)
                fh.flush()
                os.fsync(fh.fileno())
            if self._file is not None:
                fcntl.flock(self._file.fileno(), fcntl.LOCK_UN)
                self._file.close()
            os.replace(tmp, self.path)
            # The rename is durable only once the DIRECTORY entry is
            # synced — without this, a crash after the replace can
            # resurrect the pre-snapshot file (with its now-truncated
            # WAL gone), silently losing the snapshot.
            ingest_wal._fsync_dir(self.path)
            self._file = open(self.path, "a+b")
            fcntl.flock(self._file.fileno(), fcntl.LOCK_EX | fcntl.LOCK_NB)
            self._op_n = 0
            if self._wal is not None:
                # Every op the WAL covers is captured by the (now
                # durable) snapshot: restart the segment at the new
                # base version.  len(data) is the fresh file's op
                # region offset, identifying WHICH snapshot this
                # segment was truncated against.
                self._wal.truncate_segment(len(data))
            # reference: fragment.go:1026-1030
            self.stats.histogram("snapshot", time.perf_counter() - t0)

    # ------------------------------------------------------------------
    # TopN engine (reference: fragment.go:505-673)
    # ------------------------------------------------------------------

    def top(self, opt: TopOptions | None = None) -> list[Pair]:
        """Concurrent-read safe: the candidate listing and the plane
        gather each take the fragment lock briefly, but the device score
        fetch runs OUTSIDE it (the gathered submatrix is an immutable
        device snapshot) — so parallel TopN queries overlap their device
        round trips instead of serializing on the fragment, matching the
        reference's RWMutex read-side concurrency (fragment.go:507)."""
        return self.top_finish(self.top_prepare(opt))

    def top_prepare(self, opt: TopOptions | None = None) -> "TopState":
        """Phase 1 of TopN on this fragment: candidate selection, sparse
        scoring, and the ASYNC dispatch of the dense score kernel —
        everything except the device->host fetch.  The executor prepares
        every local slice first and fetches ALL their score vectors in
        one device round trip (mapperLocal's TPU shape: one transfer per
        node per phase, not one per slice)."""
        opt = opt or TopOptions()
        with self._mu:
            ids, cnts = self._top_candidates_arrays(opt.row_ids)
        return self._top_score_prepare(ids, cnts, opt, bool(opt.row_ids))

    def top_prepare_parts(self, opt: TopOptions | None = None):
        """top_prepare WITHOUT the dense-kernel dispatch: returns
        ``(TopState, sub, src_words)`` so the executor can batch many
        fragments' score kernels into one program (see
        bp.score_planes)."""
        opt = opt or TopOptions()
        with self._mu:
            ids, cnts = self._top_candidates_arrays(opt.row_ids)
        return self._top_score_parts(ids, cnts, opt, bool(opt.row_ids))

    def top_finish(self, st: "TopState") -> list[Pair]:
        """Phase 2: resolve the dense score fetch (or accept one already
        fetched in bulk via ``st.counts``) and apply the final
        threshold/tanimoto selection.  Expressed over
        ``top_score_arrays`` so the scoring arithmetic has exactly one
        implementation."""
        ids, cnts, keep, short = self.top_score_arrays(st)
        if not short:
            ids, cnts = ids[keep], cnts[keep]
            order = np.lexsort((ids, -cnts))  # sort_pairs' (-count, id)
            if st.n:
                order = order[: st.n]
            ids, cnts = ids[order], cnts[order]
        return [Pair(int(i), int(c)) for i, c in zip(ids, cnts)]

    def top_candidates_arrays(
        self, opt: TopOptions | None = None
    ) -> tuple[np.ndarray, np.ndarray]:
        """(ids, cached counts) of the filtered candidate listing phase-1
        scoring would use (cache ranking + threshold/tanimoto-window/attr
        filters) — host-only, no device work, array-native.  The
        executor's folded TopN uses this to form the cross-slice
        candidate union before any scoring dispatch."""
        opt = opt or TopOptions()
        with self._mu:
            ids, cnts = self._top_candidates_arrays(opt.row_ids)
        ids, cnts, _, _ = self._filter_arrays(ids, cnts, opt)
        return ids, cnts

    def _filter_arrays(
        self, ids: np.ndarray, cnts: np.ndarray, opt: TopOptions
    ) -> tuple[np.ndarray, np.ndarray, int, int]:
        """Candidate filtering on cached counts, vectorized (reference:
        fragment.go:535-594 candidate loop).  Returns
        ``(ids, cnts, tanimoto, src_count)`` with the filters applied;
        attr filters fall back to a per-survivor dict probe (they need
        the attr store either way)."""
        tanimoto = 0
        src_count = 0
        mask = cnts > 0
        if opt.tanimoto_threshold > 0 and opt.src is not None:
            tanimoto = opt.tanimoto_threshold
            src_count = opt.src.count()
            min_tan = float(src_count * tanimoto) / 100
            max_tan = float(src_count * 100) / float(tanimoto)
            mask &= (cnts > min_tan) & (cnts < max_tan)
        elif opt.min_threshold:
            mask &= cnts >= opt.min_threshold
        if opt.filter_field and opt.filter_values:
            filters = set()
            for v in opt.filter_values:
                try:
                    filters.add(v)
                except TypeError:
                    pass
            store = self.row_attr_store
            if store is None:
                mask[:] = False
            else:
                for k in np.flatnonzero(mask):
                    attrs = store.attrs(int(ids[k]))
                    if not attrs or attrs.get(opt.filter_field) not in filters:
                        mask[k] = False
        return ids[mask], cnts[mask], tanimoto, src_count

    @staticmethod
    def select_winners(
        ids: np.ndarray,
        cnts: np.ndarray,
        keep: np.ndarray,
        cand_ids: np.ndarray,
        n: int,
        cand_mask: np.ndarray | None = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Phase-1 winner selection over a scored union restricted to
        ``cand_ids``: filter mask, (-count, id) sort (sort_pairs'
        canonical order), trim to ``n``.  The ONE implementation of the
        phase-1 selection rule (consumed by the executor's folded
        TopN).  ``cand_mask`` optionally pre-resolves the
        ``isin(ids, cand_ids)`` membership (the executor's prep cache
        computes it once per query shape)."""
        m = keep & (
            cand_mask if cand_mask is not None else np.isin(ids, cand_ids)
        )
        sel_ids, sel_cnts = ids[m], cnts[m]
        order = np.lexsort((sel_ids, -sel_cnts))
        if n:
            order = order[:n]
        return sel_ids[order], sel_cnts[order]

    _EMPTY_I64 = np.empty(0, np.int64)

    def _top_score_prepare(
        self,
        ids: np.ndarray,
        cached: np.ndarray,
        opt: TopOptions,
        row_ids_mode: bool,
    ) -> "TopState":
        st, sub_ref, src_words = self._top_score_parts(
            ids, cached, opt, row_ids_mode
        )
        if sub_ref is not None:
            # ASYNC dispatch — the fetch happens in top_finish.  The
            # gather reads sub_ref.plane (the snapshot captured under
            # the lock), never the live mirror: a concurrent write
            # could reorder the slot layout out from under the
            # prepared slot indices.
            device_mod.pool().touch(self._pool_key)
            st.dev_counts = bp.top_counts(
                sub_ref.plane[sub_ref.slots], src_words[: sub_ref.shape[1]]
            )
        return st

    def _top_score_parts(
        self,
        ids: np.ndarray,
        cached: np.ndarray,
        opt: TopOptions,
        row_ids_mode: bool,
    ):
        """Everything in a scoring pass EXCEPT the dense-kernel
        dispatch: returns ``(TopState, sub, src_words)`` where ``sub``
        (the gathered device submatrix, or None) and ``src_words`` let
        the executor score MANY fragments in one batched program
        (bp.score_planes) instead of one dispatch per slice.

        ``ids``/``cached`` are the (unfiltered) candidate arrays in
        count-descending order; ``row_ids_mode`` mirrors the reference's
        explicit-ids behavior of returning every scored row (n applies
        only to ranked-cache candidates, reference: fragment.go:516)."""
        n = 0 if row_ids_mode else opt.n
        ids, cached, tanimoto, src_count = self._filter_arrays(ids, cached, opt)

        if opt.src is None:
            # No intersection: cached counts are final.  Candidates are
            # already count-descending; take the first n.
            if n and n < len(ids):
                ids, cached = ids[:n], cached[:n]
            return TopState(done_ids=ids, done_cnts=cached), None, None

        # Batched intersection scoring: one fused kernel over all
        # candidate rows at once (replaces the reference's sequential
        # threshold-pruned loop, fragment.go:601-627).
        if not len(ids):
            return (
                TopState(done_ids=self._EMPTY_I64, done_cnts=self._EMPTY_I64),
                None,
                None,
            )
        src_seg = opt.src.segments.get(self.slice)
        if src_seg is None:
            return (
                TopState(done_ids=self._EMPTY_I64, done_cnts=self._EMPTY_I64),
                None,
                None,
            )
        src_words = np.asarray(src_seg, dtype=np.uint32)
        with self._mu:
            dense_pos, sparse_pos, slots = self._tier_split_locked(ids)
            if not len(dense_pos) and not len(sparse_pos):
                return (
                    TopState(
                        done_ids=self._EMPTY_I64, done_cnts=self._EMPTY_I64
                    ),
                    None,
                    None,
                )
            # Candidate rows gather from the HBM-resident plane — only
            # the src row and slot indices travel host->device.  The
            # gather itself is LAZY (SubRef): the executor's
            # stacked-batch cache usually already holds the rows.
            sub_ref = self._sub_ref_locked(slots) if slots is not None else None
            # Sparse candidates (the low-count tail) score host-side in
            # O(set bits): probe src's words at each offset.
            sparse_cnt = np.empty(len(sparse_pos), np.int64)
            for j, k in enumerate(sparse_pos):
                offs = self._sparse[int(ids[k])]
                sparse_cnt[j] = int(
                    ((src_words[offs >> 5] >> (offs & np.uint32(31)))
                     & np.uint32(1)).sum()
                )
        st = TopState(
            cand_ids=ids,
            cand_cached=cached,
            dense_pos=dense_pos,
            sparse_pos=sparse_pos,
            sparse_cnt=sparse_cnt,
            n=n,
            tanimoto=tanimoto,
            src_count=src_count,
            min_threshold=opt.min_threshold,
        )
        return st, sub_ref, src_words

    def _tier_key_arrays_locked(self):
        """Sorted key arrays of the two row tiers, cached per fragment
        version: ``(slot_ids_sorted, slot_vals_aligned, sparse_ids_
        sorted)`` — turns the per-candidate dict membership walk into
        three vector ops.  Callers hold ``_mu``."""
        if self._tier_arrays is None or self._tier_arrays_version != self._version:
            sids = np.fromiter(self._slot_of.keys(), np.int64, len(self._slot_of))
            svals = np.fromiter(
                self._slot_of.values(), np.int64, len(self._slot_of)
            )
            order = np.argsort(sids)
            spids = np.sort(
                np.fromiter(self._sparse.keys(), np.int64, len(self._sparse))
            )
            self._tier_arrays = (sids[order], svals[order], spids)
            self._tier_arrays_version = self._version
        return self._tier_arrays

    def _tier_split_locked(self, ids: np.ndarray):
        """``(dense_pos, sparse_pos, slots)`` of candidate ``ids``:
        their positions by row tier, and the dense ones' plane slots as
        the int32 vector a SubRef carries (None without a dense
        candidate).  Callers hold ``_mu``."""
        slot_ids, slot_vals, sparse_sorted = self._tier_key_arrays_locked()
        dense_pos = np.flatnonzero(np.isin(ids, slot_ids))
        sparse_pos = np.flatnonzero(np.isin(ids, sparse_sorted))
        if not len(dense_pos):
            return dense_pos, sparse_pos, None
        slots = slot_vals[np.searchsorted(slot_ids, ids[dense_pos])].astype(
            np.int32
        )
        # Pad to a full row block (repeating the last slot) so the
        # scorer's row count stays on the tile-aligned kernel path;
        # surplus scores are discarded on read.
        padded = bp.pad_rows(len(slots))
        if padded != len(slots):
            slots = np.pad(slots, (0, padded - len(slots)), mode="edge")
        return dense_pos, sparse_pos, slots

    def _sub_ref_locked(self, slots: np.ndarray) -> SubRef:
        """The scorer's view of this fragment for padded ``slots``, on
        the CURRENT mirror.  Callers hold ``_mu``, so the snapshot and
        whatever slots they read under the same hold agree.  The mirror
        is not touched in the residency pool here: who scores the
        SubRefs of many fragments touches them all at once
        (``_mirror_locked``)."""
        return SubRef(
            plane=self._mirror_locked(),
            slots=slots,
            shape=(len(slots), int(self._plane.shape[1])),
            plane_rows=int(self._plane.shape[0]),
            device=bp.home_device(self.slice),
        )

    def top_layout(self) -> TopLayout:
        """The gather layout of this fragment's own ranked candidates
        (TopLayout): the one kept, while no write (``_version``) and no
        re-sort of the rank cache (``top_arrays`` hands out new arrays)
        has happened since it was made, so a distinct TopN text asks
        nothing here that the last one did not.  A re-sort that lists
        the same rows (new counts at most: the throttled re-sort of an
        unwritten fragment) keeps the tier split and the slots, which
        depend on the ids and the version alone.  The listing goes
        through ``_top_candidates_arrays``, so the rank cache's
        throttled re-sort happens exactly where it did."""
        with self._mu:
            ranked = self._top_candidates_arrays(None)
            lay = self._top_layout
            fresh = lay is not None and lay.version == self._version
            if fresh and lay.ranked is ranked:
                return lay
            ids, cnts = ranked
            keep = cnts > 0
            if not keep.all():
                ids, cnts = ids[keep], cnts[keep]
            if fresh and np.array_equal(ids, lay.ids):
                split = lay.dense_pos, lay.sparse_pos, lay.slots
            else:
                split = self._tier_split_locked(ids)
            lay = self._top_layout = TopLayout(
                ids, cnts, *split, version=self._version, ranked=ranked
            )
            return lay

    def rows_layout(self) -> RowsLayout | None:
        """The ranked candidates by plane slot (RowsLayout), kept like
        ``top_layout``'s while no write and no re-sort of the rank cache
        has happened; None where a ranked row lives in the sparse tier
        (no plane holds it: the general walk scores it on the host).
        The counts go to the device once a layout, under a pool entry
        of their own beside the mirror's."""
        import jax

        with self._mu:
            ranked = self._top_candidates_arrays(None)
            lay = self._rows_layout
            if lay is not None and lay.version == self._version and lay.ranked is ranked:
                device_mod.pool().touch(self._rows_pool_key)
                return lay
            ids, cnts = ranked
            order = np.argsort(ids, kind="stable")
            slots, _absent = self._slot_table_locked(ids[order])
            if (slots < 0).any():
                return None
            by_slot = np.full(self._plane.shape[0], -1, dtype=np.int64)
            by_slot[slots] = ids[order]
            counts = np.zeros(self._plane.shape[0], dtype=np.int32)
            counts[slots] = cnts[order]
            dev = bp.home_device(self.slice)
            device_mod.pool().admit(
                self._rows_pool_key,
                {dev: int(counts.nbytes)},
                self._evict_rows_layout,
                category="cache",
                info=self._pool_info(),
            )
            lay = self._rows_layout = RowsLayout(
                by_slot,
                jax.device_put(counts, dev),
                np.sort(cnts),
                self._version,
                ranked,
            )
            return lay

    def _evict_rows_layout(self) -> bool:
        """Residency-pool eviction hook for the layout's device counts
        (rebuilt on the next ``rows_layout``)."""
        if not self._mu.acquire(blocking=False):
            return False
        try:
            self._rows_layout = None
            return True
        finally:
            self._mu.release()

    def rows_mirror(self, lay: RowsLayout, src_row: int):
        """``(mirror snapshot, src slot)`` for a walk of ``lay``'s rows
        against row ``src_row`` of this very plane, both read under one
        hold of the lock; None where the layout no longer holds (a
        write since) or the src is not a dense-tier row here."""
        with self._mu:
            slot = self._slot_of.get(src_row)
            if slot is None or lay.version != self._version:
                return None
            return self._mirror_locked(), slot

    def score_rows_host(self, lay: RowsLayout, src_slot: int, src_count: int,
                        tanimoto: int, min_threshold: int):
        """``bp.score_rows`` on the host's plane: the device-health
        gate's fallback, same rules, same ``(slots, shared bits)``."""
        with self._mu:
            plane = self._plane
            src = plane[src_slot].copy()
        step = max(256, (1 << 22) // plane.shape[1])
        c = np.concatenate(
            [
                bp.np_row_counts(plane[b : b + step] & src)
                for b in range(0, plane.shape[0], step)
            ]
        )
        cnts = np.asarray(lay.cnts).astype(np.int64)
        s, t = src_count, tanimoto
        if t > 0:
            keep = (
                (100 * cnts > s * t)
                & (cnts * t < 100 * s)
                & (100 * c > t * (cnts + s - c))
            )
        else:
            keep = (cnts >= min_threshold) & (c >= min_threshold)
        at = np.flatnonzero(keep & (cnts > 0) & (c > 0))
        return at, c[at]

    def top_prepare_own_parts(
        self, lay: TopLayout, min_threshold: int, src_row: int | None
    ):
        """``top_prepare_union_parts`` for a union that IS ``lay``'s
        candidates, under options whose filters reduce to ``count > 0``
        (``TopOptions.keeps_every_counted``) and a src that is row
        ``src_row`` of this very fragment (None: no src).  Nothing is
        foreign, so the TopState and the SubRef come from the layout:
        no set algebra.  And no host copy of the src row: a
        dense-tier src is scored from its slot in the plane
        (``TopState.src_row``; the executor asks for the slot).

        Returns ``(TopState, SubRef, None)`` as the ``*_parts`` APIs
        do, or None where the layout cannot serve and the caller takes
        the general way: a write since it was derived, a sparse-tier
        candidate (probing it reads the src's host words), or a src row
        that is not in the dense tier here."""
        if src_row is None:
            # No intersection: cached counts are final.
            return TopState(done_ids=lay.ids, done_cnts=lay.cnts), None, None
        if lay.slots is None or len(lay.sparse_pos):
            return None
        with self._mu:
            if lay.version != self._version or src_row not in self._slot_of:
                return None
            sub_ref = self._sub_ref_locked(lay.slots)
        st = TopState(
            cand_ids=lay.ids,
            cand_cached=lay.cnts,
            dense_pos=lay.dense_pos,
            sparse_pos=lay.sparse_pos,
            sparse_cnt=self._EMPTY_I64,
            min_threshold=min_threshold,
            src_row=src_row,
        )
        return st, sub_ref, None

    def dense_rows(self) -> tuple[np.ndarray, np.ndarray]:
        """``(row ids ascending, their slots)`` of the dense tier: where
        a src row lies in the plane, for every row at once (the
        executor's kept TopN stack looks a text's src up in them)."""
        with self._mu:
            return self._tier_key_arrays_locked()[:2]

    def top_score_arrays(
        self, st: "TopState"
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray, bool]:
        """Vectorized view of a scoring pass: ``(ids, counts, keep,
        done)`` over the candidates in candidate order, where ``keep``
        is the threshold/tanimoto filter mask ``top_finish`` would apply
        element-wise.  ``done=True`` means the pass short-circuited and
        ``ids/counts`` are that final, already-filtered list with
        ``keep`` all-true.

        The folded executor TopN consumes this instead of ``top_finish``:
        at 2k candidates x several calls per query, building and merging
        Pair objects in Python dominated warm TopN host time; the numpy
        formulation does the identical arithmetic in a few vector ops.
        """
        if st.done_ids is not None:
            return (
                st.done_ids,
                st.done_cnts,
                np.ones(len(st.done_ids), dtype=bool),
                True,
            )
        ids, cached = st.cand_ids, st.cand_cached
        cnts = np.zeros(len(ids), np.int64)
        if st.dense_pos is not None and len(st.dense_pos):
            if st.counts is None:
                st.counts = np.asarray(st.dev_counts)
            cnts[st.dense_pos] = np.asarray(
                st.counts[: len(st.dense_pos)], dtype=np.int64
            )
        if st.sparse_pos is not None and len(st.sparse_pos):
            cnts[st.sparse_pos] = st.sparse_cnt
        if st.tanimoto > 0:
            denom = cached + st.src_count - cnts
            with np.errstate(divide="ignore", invalid="ignore"):
                score = np.ceil(cnts * 100.0 / denom)
            keep = (cnts > 0) & (score > st.tanimoto)
        else:
            keep = (cnts > 0) & (cnts >= st.min_threshold)
        return ids, cnts, keep, False

    def _top_candidates_arrays(
        self, row_ids: list[int] | None
    ) -> tuple[np.ndarray, np.ndarray]:
        """reference: fragment.go:641-673 topBitmapPairs"""
        if not row_ids:
            # invalidate() is throttle-aware: the re-sort happens at most
            # every RECALCULATE_INTERVAL_S (reference: cache.go:236-241).
            self.cache.invalidate()
            return self.cache.top_arrays()
        ids, cnts = [], []
        # Dedupe explicit ids: a duplicated id would be scored twice and
        # its counts SUMMED by the cross-slice merge (and break the
        # assume_unique contract of top_prepare_union's setdiff).
        for row_id in dict.fromkeys(row_ids):
            c = self._row_count_locked(row_id)
            if c > 0:
                ids.append(row_id)
                cnts.append(c)
        ids = np.asarray(ids, np.int64)
        cnts = np.asarray(cnts, np.int64)
        order = np.lexsort((ids, -cnts))
        return ids[order], cnts[order]

    def _row_count_locked(self, row_id: int) -> int:
        """Count resolution for candidate listing (callers hold _mu):
        cached ranking first, then the O(1) maintained count, with
        full-row materialization (128 KiB unpack) only as a consistency
        safety net."""
        n = self.cache.get(row_id)
        if n <= 0 and (row_id in self._slot_of or row_id in self._sparse):
            n = self._count_of.get(row_id, 0)
            if n <= 0:
                n = self.row(row_id).count()
        return n

    def top_prepare_union_parts(
        self,
        union_ids: np.ndarray,
        cand_ids: np.ndarray,
        cand_cnts: np.ndarray,
        opt: TopOptions,
    ):
        """The folded executor TopN's union scoring pass WITHOUT the
        dense-kernel dispatch (see top_prepare_parts): equivalent to
        ``top_prepare(replace(opt, row_ids=union))`` but reuses the
        already-listed candidate arrays, resolving counts only for
        union ids this slice's own cache walk didn't produce (foreign
        winners) — O(missing) host work instead of O(union).
        ``union_ids`` must be unique (np.unique output)."""
        with self._mu:
            foreign = np.setdiff1d(union_ids, cand_ids, assume_unique=True)
            f_cnts = np.fromiter(
                (self._row_count_locked(int(r)) for r in foreign),
                np.int64,
                len(foreign),
            )
        fm = f_cnts > 0
        all_ids = np.concatenate([cand_ids, foreign[fm]])
        all_cnts = np.concatenate([cand_cnts, f_cnts[fm]])
        order = np.lexsort((all_ids, -all_cnts))
        return self._top_score_parts(
            all_ids[order], all_cnts[order], opt, row_ids_mode=True
        )

    # ------------------------------------------------------------------
    # block checksums + sync (reference: fragment.go:694-934)
    # ------------------------------------------------------------------

    def checksum(self) -> bytes:
        """SHA1 over the block checksums (reference: fragment.go:694-701)."""
        h = hashlib.sha1()
        for _, chk in self.blocks():
            h.update(chk)
        return h.digest()

    def blocks(self) -> list[tuple[int, bytes]]:
        """[(block_id, sha1)] per HASH_BLOCK_SIZE rows; empty blocks are
        skipped (reference: fragment.go:717-796).  Checksums hash the
        sorted (row, offset) BIT POSITIONS of the block — like the
        reference, which hashes positions rather than raw storage — so
        they depend only on logical content, identical across tiers and
        replicas."""
        with self._mu:
            by_block: dict[int, list[int]] = {}
            for r in self._slot_of:
                by_block.setdefault(r // HASH_BLOCK_SIZE, []).append(r)
            for r in self._sparse:
                by_block.setdefault(r // HASH_BLOCK_SIZE, []).append(r)
            out = []
            for block_id in sorted(by_block):
                if (
                    block_id in self._block_sums
                    and block_id not in self._dirty_blocks
                ):
                    chk = self._block_sums[block_id]
                else:
                    rws, cls = self._block_positions(
                        block_id, by_block[block_id]
                    )
                    chk = (
                        hashlib.sha1(
                            rws.astype("<u8").tobytes()
                            + cls.astype("<u8").tobytes()
                        ).digest()
                        if len(rws)
                        else None
                    )
                    self._block_sums[block_id] = chk
                    self._dirty_blocks.discard(block_id)
                if chk is not None:
                    out.append((block_id, chk))
            return out

    def _block_positions(
        self, block_id: int, rows: list[int] | None = None
    ) -> tuple[np.ndarray, np.ndarray]:
        """Sorted (rows, col-offsets) of every set bit in a block, from
        both tiers.  ``rows`` (any order) skips the full-dict scan when
        the caller already grouped rows by block — blocks() would
        otherwise rescan every row per block."""
        lo = block_id * HASH_BLOCK_SIZE
        hi = lo + HASH_BLOCK_SIZE
        if rows is None:
            rows = [r for r in self._slot_of if lo <= r < hi] + [
                r for r in self._sparse if lo <= r < hi
            ]
        rows = sorted(rows)
        segs: list[np.ndarray] = []
        seg_rows: list[int] = []
        for r in rows:
            slot = self._slot_of.get(r)
            if slot is not None:
                offs = bp.np_row_to_columns(self._plane[slot]).astype(np.int64)
            else:
                offs = self._sparse[r].astype(np.int64)
            if len(offs):
                segs.append(offs)
                seg_rows.append(r)
        if not segs:
            return np.empty(0, np.int64), np.empty(0, np.int64)
        lens = np.asarray([len(s) for s in segs])
        rws = np.repeat(np.asarray(seg_rows, dtype=np.int64), lens)
        return rws, np.concatenate(segs)

    def block_data(self, block_id: int) -> PairSet:
        """All (row, col-offset) bits in a block (reference:
        fragment.go:798-808)."""
        with self._mu:
            rws, cls = self._block_positions(block_id)
            # .tolist() materializes Python ints in C, not per-element
            # Python-loop conversion.
            return PairSet(row_ids=rws.tolist(), column_ids=cls.tolist())

    def merge_block(
        self, block_id: int, data: list[PairSet]
    ) -> tuple[list[PairSet], list[PairSet]]:
        """Majority-consensus merge of replicas' block data (reference:
        fragment.go:810-934): a bit is set iff >= (n+1+1)//2 of the n+1
        participants have it (ties -> set).  Applies the local diff and
        returns (sets, clears) per *remote* participant.

        Note: the reference has a bookkeeping slip in its clears-diff
        construction (clears[i].RowIDs appended from sets[i].RowIDs,
        fragment.go:913); this implementation computes the clears
        correctly rather than reproducing the bug.
        """
        for i, ps in enumerate(data):
            if len(ps.row_ids) != len(ps.column_ids):
                raise FragmentError(
                    f"pair set mismatch(idx={i}): "
                    f"{len(ps.row_ids)} != {len(ps.column_ids)}"
                )
        with self._mu:
            lo_row = block_id * HASH_BLOCK_SIZE
            hi_row = (block_id + 1) * HASH_BLOCK_SIZE

            local = self.block_data(block_id)
            participants = [local] + list(data)

            def to_pos(ps: PairSet) -> np.ndarray:
                if not ps.row_ids:
                    return np.empty(0, dtype=np.int64)
                r = np.asarray(ps.row_ids, dtype=np.int64)
                c = np.asarray(ps.column_ids, dtype=np.int64)
                keep = (r >= lo_row) & (r < hi_row) & (c >= 0) & (c < SLICE_WIDTH)
                return np.unique(r[keep] * SLICE_WIDTH + c[keep])

            pos_sets = [to_pos(ps) for ps in participants]
            all_pos = np.concatenate(pos_sets) if pos_sets else np.empty(0, np.int64)
            if all_pos.size == 0:
                return ([PairSet() for _ in data], [PairSet() for _ in data])
            uniq, votes = np.unique(all_pos, return_counts=True)
            majority_n = (len(participants) + 1) // 2
            consensus = votes >= majority_n

            sets_out: list[PairSet] = []
            clears_out: list[PairSet] = []
            for pos in pos_sets:
                has = np.isin(uniq, pos)
                to_set = uniq[consensus & ~has]
                to_clear = uniq[~consensus & has]
                sets_out.append(
                    PairSet(
                        row_ids=[int(p) // SLICE_WIDTH for p in to_set],
                        column_ids=[int(p) % SLICE_WIDTH for p in to_set],
                    )
                )
                clears_out.append(
                    PairSet(
                        row_ids=[int(p) // SLICE_WIDTH for p in to_clear],
                        column_ids=[int(p) % SLICE_WIDTH for p in to_clear],
                    )
                )

            base = self.slice * SLICE_WIDTH
            for r, c in zip(sets_out[0].row_ids, sets_out[0].column_ids):
                self.set_bit(r, base + c)
            for r, c in zip(clears_out[0].row_ids, clears_out[0].column_ids):
                self.clear_bit(r, base + c)

            return sets_out[1:], clears_out[1:]

    # ------------------------------------------------------------------
    # archive backup/restore (reference: fragment.go:1112-1283)
    # ------------------------------------------------------------------

    def _archive_payloads(self) -> list[tuple[str, bytes]]:
        """Consistent snapshot of the archive entries, taken under the
        lock; serialization to tar happens lock-free so a slow consumer
        never stalls writers.

        The archive SELF-VERIFIES: a leading "checksum" entry carries
        the sha256 of every payload entry, so restore (and the tier
        store's get) rejects torn bytes with
        :class:`ArchiveChecksumError` instead of installing them —
        previously only ``rebalance/`` checksummed, out-of-band."""
        with self._mu:
            data = roaring.encode_packed(*self._containers_packed())
            cache_data = self._encode_cache_ids(self.cache.ids())
        sums = json.dumps(
            {
                "algo": "sha256",
                "entries": {
                    "data": hashlib.sha256(data).hexdigest(),
                    "cache": hashlib.sha256(cache_data).hexdigest(),
                },
            },
            separators=(",", ":"),
        ).encode()
        return [("checksum", sums), ("data", data), ("cache", cache_data)]

    @staticmethod
    def _write_archive(entries: list[tuple[str, bytes]], w) -> None:
        tw = tarfile.open(fileobj=w, mode="w|")
        for name, payload in entries:
            info = tarfile.TarInfo(name)
            info.size = len(payload)
            info.mtime = int(time.time())
            tw.addfile(info, io.BytesIO(payload))
        tw.close()

    def write_to(self, w) -> None:
        """Stream a tar with "data" (roaring file) and "cache" entries."""
        self._write_archive(self._archive_payloads(), w)

    def tar_chunks(self, chunk_bytes: int = 0) -> Iterable[bytes]:
        """The archive as a bounded-chunk generator: the tar writer
        runs against a ChunkPipe on a producer thread, so the HTTP
        layer pulls constant-size chunks with backpressure instead of
        materializing the tar (reference: handler.go:1102-1123 +
        fragment.go:1112-1176 stream WriteTo into the ResponseWriter)."""
        from pilosa_tpu import stream as stream_mod

        entries = self._archive_payloads()
        return stream_mod.generate_from_writer(
            lambda w: self._write_archive(entries, w), chunk_bytes=chunk_bytes
        )

    @staticmethod
    def _verify_archive_payloads(payloads: dict[str, bytes]) -> None:
        """Check every payload entry against the tar's embedded
        "checksum" entry (when present — archives from before the
        tiered-storage PR have none and install unverified, like the
        reference's).  Raises :class:`ArchiveChecksumError` BEFORE any
        payload is applied, so a torn transfer never half-installs."""
        chk = payloads.pop("checksum", None)
        if chk is None:
            return
        try:
            entries = json.loads(chk).get("entries", {})
        except (ValueError, AttributeError) as e:
            raise ArchiveChecksumError(
                f"fragment archive has an unreadable checksum entry: {e}"
            ) from e
        for name, want in entries.items():
            payload = payloads.get(name)
            if payload is None:
                continue  # entry legitimately absent from this archive
            got = hashlib.sha256(payload).hexdigest()
            if got != want:
                raise ArchiveChecksumError(
                    f"fragment archive entry {name!r} is torn: sha256 "
                    f"{got[:12]}… != recorded {str(want)[:12]}…"
                )

    def read_from(self, r) -> None:
        """Restore from a tar produced by write_to.  Payloads are
        collected and CHECKSUM-VERIFIED first (see
        :meth:`_verify_archive_payloads`), then applied data-then-cache
        — a rejected archive leaves the fragment untouched."""
        with self._mu:
            tr = tarfile.open(fileobj=r, mode="r|")
            payloads: dict[str, bytes] = {}
            for member in tr:
                payloads[member.name] = tr.extractfile(member).read()
            tr.close()
            self._verify_archive_payloads(payloads)
            payload = payloads.get("data")
            if payload is not None:
                words, arrays, _ = roaring.decode_tiered(payload)
                self._load_tiered(words, arrays)
                self._version += 1
                self._row_cache.clear()
                self._op_n = 0
                self._op_buf.clear()  # replaced wholesale below
                # persist (same durability discipline as snapshot():
                # file fsync before the atomic rename, directory fsync
                # after — a crash must never resurrect the pre-restore
                # file once the restore was acked)
                with open(self.path + ".snapshotting", "wb") as fh:
                    fh.write(payload)
                    fh.flush()
                    os.fsync(fh.fileno())
                if self._file is not None:
                    fcntl.flock(self._file.fileno(), fcntl.LOCK_UN)
                    self._file.close()
                os.replace(self.path + ".snapshotting", self.path)
                ingest_wal._fsync_dir(self.path)
                self._file = open(self.path, "a+b")
                fcntl.flock(self._file.fileno(), fcntl.LOCK_EX | fcntl.LOCK_NB)
                if self._wal is not None:
                    # Restored content replaces everything the segment
                    # described: restart it against the new snapshot.
                    self._wal.truncate_segment(len(payload))
            cache_payload = payloads.get("cache")
            if cache_payload is not None:
                ids = self._decode_cache_ids(cache_payload)
                if ids is not None:
                    self.cache = cache_mod.new_cache(
                        self.cache_type, self.cache_size
                    )
                    self.cache.stats = self.stats
                    for row_id in ids:
                        if isinstance(row_id, int) and (
                            row_id in self._slot_of or row_id in self._sparse
                        ):
                            self.cache.bulk_add(
                                row_id, self._count_of.get(row_id, 0)
                            )
                    self.cache.invalidate()
                    # A replaced cache changes TopN candidates without
                    # any fragment write: epoch-validated prep caches
                    # must notice even for a cache-only tar (the data
                    # branch bumps via _load_tiered).
                    _bump_write_epoch()

    # ------------------------------------------------------------------

    def _iter_row_offsets(self) -> Iterable[tuple[int, np.ndarray]]:
        """Yield (rowID, sorted uint64 offsets-within-slice) per non-empty
        row, ascending, taking the lock per row (reference:
        fragment.go:487-502 over the container iterators).  The single
        iteration protocol under both for_each_bit and csv_chunks.

        Peak extra memory is ONE unpacked row (~1 MiB), not the fully
        unpacked plane — exports and sync walks of big fragments stay
        under 2x plane memory."""
        with self._mu:
            rows = sorted(set(self._slot_of) | set(self._sparse))
        for r in rows:
            with self._mu:
                slot = self._slot_of.get(r)
                if slot is not None:
                    offs = bp.np_row_to_columns(self._plane[slot])
                else:
                    sp = self._sparse.get(r)
                    if sp is None:
                        continue
                    offs = sp
            if len(offs):
                yield r, offs

    def for_each_bit(self) -> Iterable[tuple[int, int]]:
        """Yield (rowID, absolute columnID) for every set bit."""
        base = self.slice * SLICE_WIDTH
        for r, offs in self._iter_row_offsets():
            for c in offs:
                yield r, base + int(c)

    def csv_chunks(self, chunk_pairs: int = 1 << 20) -> Iterable[bytes]:
        """Vectorized CSV export: yield "row,col\\n" byte chunks of up to
        ``chunk_pairs`` records, rows ascending (reference: the
        fragment.go:487-502 iterator feeding ctl/export.go — but
        formatted a row-block at a time through the native formatter
        instead of one Python tuple per bit)."""
        base = self.slice * SLICE_WIDTH
        pend_r: list[np.ndarray] = []
        pend_c: list[np.ndarray] = []
        pending = 0
        for r, offs in self._iter_row_offsets():
            pend_r.append(np.full(len(offs), r, dtype=np.uint64))
            pend_c.append(offs.astype(np.uint64) + np.uint64(base))
            pending += len(offs)
            if pending >= chunk_pairs:
                yield self._format_pairs(np.concatenate(pend_r), np.concatenate(pend_c))
                pend_r, pend_c, pending = [], [], 0
        if pending:
            yield self._format_pairs(np.concatenate(pend_r), np.concatenate(pend_c))

    @staticmethod
    def _format_pairs(rws: np.ndarray, cls: np.ndarray) -> bytes:
        from pilosa_tpu import native

        blob = native.format_csv(rws, cls)
        if blob is not None:
            return blob
        # numpy fallback: C-loop string conversion, still no per-bit
        # Python iteration.
        out = np.char.add(
            np.char.add(rws.astype("S20"), b","),
            np.char.add(cls.astype("S20"), b"\n"),
        )
        return b"".join(out.tolist())

    def __repr__(self) -> str:
        return (
            f"Fragment(index={self.index!r}, frame={self.frame!r}, "
            f"view={self.view!r}, slice={self.slice})"
        )
