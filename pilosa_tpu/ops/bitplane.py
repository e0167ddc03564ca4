"""Dense bit-plane representation and XLA bitmap ops.

The unit of storage is a *slice-row*: one row of one fragment, covering
SLICE_WIDTH = 2^20 columns, stored as 32,768 uint32 words (128 KiB).  A
fragment is a plane of shape (rows, WORDS_PER_SLICE).  Bit ``i`` of a
slice-row (column ``slice*SLICE_WIDTH + i``) lives at word ``i >> 5``,
bit ``i & 31`` (little-endian within the word, matching the reference's
roaring bitmap-container layout where word ``w`` holds values
``[w*64, w*64+64)`` — we use uint32 words because TPUs have no uint64).

These functions replace the reference's per-container sorted-merge kernels
and popcount assembly (reference: roaring/roaring.go:1259-1716,
roaring/assembly_amd64.s) with whole-row vector ops: XLA fuses the bitwise
op into the popcount reduce, so ``count_and`` etc. never materialize the
intermediate row in HBM as one fused bitwise+popcount+reduce pass.

All counts are returned as int32 device scalars (a slice-row holds at most
2^20 bits, and a full plane reduce stays far below 2^31); callers accumulate
cross-slice totals in Python ints.
"""

from __future__ import annotations

import functools
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

# Matches the reference: SliceWidth = 2^20 (reference: fragment.go:47).
SLICE_WIDTH = 1 << 20
WORD_BITS = 32
WORDS_PER_SLICE = SLICE_WIDTH // WORD_BITS  # 32768 words = 128 KiB
# A roaring container spans 2^16 bits (reference: roaring/roaring.go:36).
CONTAINER_BITS = 1 << 16
WORDS_PER_CONTAINER = CONTAINER_BITS // WORD_BITS  # 2048
CONTAINERS_PER_SLICE = SLICE_WIDTH // CONTAINER_BITS  # 16

# Rows are padded to power-of-two shape classes (floor ROW_BLOCK) so
# query shapes bucket into a LOG-bounded set of compiled programs.  The
# former multiple-of-8 padding kept single-row growth from recompiling,
# but a churny schema still minted a fresh XLA program every 8 rows
# (~326 ms each, VERDICT item 3): plane mirrors and candidate slot
# arrays both enter jit keys by shape, so their shape-class count IS the
# compiled-program cardinality.  pow2 classes bound it at
# log2(rows/ROW_BLOCK)+1 regardless of how many distinct fragment
# shapes the schema produces.
ROW_BLOCK = 8

# Words of the narrowest plane row: 4,096 columns, one lane row of a TPU
# tile (8 such rows a tile).  A fragment's plane is as wide as the
# columns it holds ask for (``row_words``), so a frame whose rows are
# many and whose columns are few (a fingerprint a row) pays 512 B a row
# and not 128 KiB.
MIN_ROW_WORDS = 128


def row_shape() -> tuple[int]:
    return (WORDS_PER_SLICE,)


def empty_row() -> np.ndarray:
    return np.zeros(WORDS_PER_SLICE, dtype=np.uint32)


def empty_plane(rows: int, words: int = WORDS_PER_SLICE) -> np.ndarray:
    return np.zeros((rows, words), dtype=np.uint32)


def row_words(max_offset: int) -> int:
    """Words of a plane row that holds in-slice column ``max_offset``:
    the smallest power of two that covers it, never under MIN_ROW_WORDS
    nor over WORDS_PER_SLICE — a pow2 class like the rows', so the word
    axis adds log2(256) + 1 shapes to a program family at most."""
    return min(pow2_bucket((int(max_offset) >> 5) + 1, MIN_ROW_WORDS), WORDS_PER_SLICE)


def widen_row(words: np.ndarray) -> np.ndarray:
    """A row of a narrow plane as the full-width row every reader but
    the TopN scorers takes (a copy; a full-width row is copied too)."""
    row = empty_row()
    row[: words.shape[-1]] = words
    return row


def pow2_bucket(n: int, floor: int = 1) -> int:
    """Round ``n`` up to the next power of two, at least ``floor``."""
    if n <= floor:
        return floor
    return 1 << (n - 1).bit_length()


def bucket_classes(hi: int, floor: int = 1) -> int:
    """How many distinct pow2 shape classes cover sizes in [1, hi] —
    the hard bound on compiled-program cardinality per bucketed
    dimension (exec/plan.program_cache_bounds)."""
    if hi <= floor:
        return 1
    return (pow2_bucket(hi, floor) // floor).bit_length()


def pad_rows(rows: int) -> int:
    """Round a row count up to its pow2 shape class (floor ROW_BLOCK)."""
    return pow2_bucket(rows, ROW_BLOCK)


# ---------------------------------------------------------------------------
# Host-side (numpy) bit manipulation — the write path.  Mutations happen on
# the host-resident authoritative plane; device mirrors are refreshed lazily
# (see core/fragment.py).
# ---------------------------------------------------------------------------


def np_set_bit(plane: np.ndarray, bit: int) -> bool:
    """Set bit ``bit`` (a fragment position: row*SLICE_WIDTH + col%SLICE_WIDTH
    flattened into the plane).  Returns True if the bit changed."""
    row, offset = divmod(bit, SLICE_WIDTH)
    word, shift = divmod(offset, WORD_BITS)
    mask = np.uint32(1 << shift)
    old = plane[row, word]
    if old & mask:
        return False
    plane[row, word] = old | mask
    return True


def np_clear_bit(plane: np.ndarray, bit: int) -> bool:
    row, offset = divmod(bit, SLICE_WIDTH)
    word, shift = divmod(offset, WORD_BITS)
    mask = np.uint32(1 << shift)
    old = plane[row, word]
    if not (old & mask):
        return False
    plane[row, word] = old & ~mask
    return True


def np_contains(plane: np.ndarray, bit: int) -> bool:
    row, offset = divmod(bit, SLICE_WIDTH)
    word, shift = divmod(offset, WORD_BITS)
    return bool((int(plane[row, word]) >> shift) & 1)


def np_set_bulk(plane: np.ndarray, rows: np.ndarray, offsets: np.ndarray) -> None:
    """Bulk set: vectorized scatter-OR for imports (reference:
    fragment.go:936-1004 bulk Import path)."""
    words = offsets >> 5
    masks = np.uint32(1) << (offsets & 31).astype(np.uint32)
    np.bitwise_or.at(plane, (rows, words), masks)


def np_clear_bulk(plane: np.ndarray, rows: np.ndarray, offsets: np.ndarray) -> None:
    """Bulk clear: vectorized scatter-ANDNOT — the overwrite half of a
    columnar BSI value import (a re-imported column must drop the stale
    bits of its previous value)."""
    words = offsets >> 5
    masks = np.uint32(1) << (offsets & 31).astype(np.uint32)
    np.bitwise_and.at(plane, (rows, words), ~masks)


def np_row_to_columns(row_words: np.ndarray) -> np.ndarray:
    """Expand one slice-row's set bits into sorted uint64 column offsets
    within the slice (0 .. SLICE_WIDTH)."""
    bits = np.unpackbits(
        np.ascontiguousarray(row_words).view(np.uint8), bitorder="little"
    )
    (positions,) = np.nonzero(bits)
    return positions.astype(np.uint64)


def np_columns_to_row(offsets: np.ndarray, words: int = WORDS_PER_SLICE) -> np.ndarray:
    """Inverse of np_row_to_columns: bit offsets (within slice) -> row
    words (``words`` of them: the caller's plane covers the offsets)."""
    row = np.zeros(words, dtype=np.uint32)
    if len(offsets) == 0:
        return row
    offsets = np.asarray(offsets, dtype=np.uint64)
    words = (offsets // WORD_BITS).astype(np.int64)
    masks = (np.uint32(1) << (offsets % WORD_BITS).astype(np.uint32)).astype(np.uint32)
    np.bitwise_or.at(row, words, masks)
    return row


def np_plane_positions(block: np.ndarray) -> np.ndarray:
    """The set bits of ``block`` (uint32[n, words]) as uint32 in-row
    positions, row after row, ascending within a row: the values of a
    row's roaring array containers, for every row at once.  Works a set
    WORD at a time, not a bit: a plane row of few columns is mostly
    zero words, and a set word has one or two bits (``np.unpackbits``
    over a million 512 B rows writes 7 GB of bytes to find 80M)."""
    flat = block.reshape(-1)
    nz = np.flatnonzero(flat)
    v = flat[nz]
    pc = np_word_counts(v)
    at = np.cumsum(pc) - pc  # where a word's first bit goes
    # (a plane's row is a power of two of words)
    base = ((nz & (block.shape[1] - 1)) << 5).astype(np.uint32)
    out = np.empty(int(pc.sum()), dtype=np.uint32)
    one = np.uint32(1)
    while len(v):
        low = v & (~v + one)  # the lowest set bit
        out[at] = base + np_word_counts(low - one).astype(np.uint32)
        v = v ^ low
        more = v != 0
        v, at, base = v[more], at[more] + 1, base[more]
    return out


if hasattr(np, "bitwise_count"):  # numpy >= 2.0

    def np_count(words: np.ndarray) -> int:
        """Host popcount (the CPU reference path, equivalent of the
        reference's pure-Go popcntSlice fallback, reference:
        roaring/assembly.go:21-28)."""
        return int(np.bitwise_count(words).sum())

    def np_row_counts(plane: np.ndarray) -> np.ndarray:
        """Host per-row popcounts (cache maintenance without a device trip)."""
        return np.bitwise_count(plane).sum(axis=-1, dtype=np.int64)

    def np_word_counts(words: np.ndarray) -> np.ndarray:
        """Host popcount of each word."""
        return np.bitwise_count(words).astype(np.int64)

else:  # pragma: no cover - numpy 1.x fallback

    def np_count(words: np.ndarray) -> int:
        return int(np.unpackbits(np.ascontiguousarray(words).view(np.uint8)).sum())

    def np_row_counts(plane: np.ndarray) -> np.ndarray:
        return (
            np.unpackbits(np.ascontiguousarray(plane).view(np.uint8), axis=-1)
            .sum(axis=-1, dtype=np.int64)
        )

    def np_word_counts(words: np.ndarray) -> np.ndarray:
        return np_row_counts(np.ascontiguousarray(words)[:, None])


# ---------------------------------------------------------------------------
# Device ops (XLA).  Everything below is jit-compiled; shapes are static per
# (rows,) bucket.  These are the hot kernels: the equivalents of the
# reference's popcntAndSlice/popcntOrSlice/popcntXorSlice asm procs and the
# materializing container merges.
# ---------------------------------------------------------------------------


def _popcount_sum(words: jnp.ndarray) -> jnp.ndarray:
    """Sum of set bits over the whole array -> int32 scalar."""
    return jnp.sum(jax.lax.population_count(words).astype(jnp.int32))


@functools.lru_cache(maxsize=8)
def _parse_mesh_shape(shape: str) -> int | None:
    """Device cap from a mesh-shape string ("4", "4x2", ...); None when
    unset, malformed, or non-positive (a bad value must never silently
    disable sharding)."""
    factors = shape.lower().replace("x", " ").split()
    if not factors:
        return None
    try:
        want = 1
        for f in factors:
            want *= int(f)
    except ValueError:
        return None
    return want if want >= 1 else None


# [device] mesh-devices override (Server.open / bench / tests): the
# process-global device-count cap for slice placement and the slices
# mesh.  0 = unset (fall through to the envs, default all visible
# devices); 1 = force the single-device data plane; N caps the mesh.
_MESH_DEVICES_OVERRIDE = 0


def configure_mesh_devices(n: int) -> None:
    """Set (or with 0, clear) the process-wide ``[device] mesh-devices``
    cap.  Placement is process-global state — in-process multi-server
    setups (tests, bench grids) share whatever the last caller set."""
    global _MESH_DEVICES_OVERRIDE
    _MESH_DEVICES_OVERRIDE = max(0, int(n))


def _mesh_devices_cap() -> int | None:
    """The effective device cap: explicit configure_mesh_devices wins,
    then ``PILOSA_DEVICE_MESH_DEVICES`` (0 = all visible), then the
    legacy ``PILOSA_TPU_MESH_SHAPE`` factor product; None = uncapped.
    Malformed values never silently disable sharding."""
    if _MESH_DEVICES_OVERRIDE > 0:
        return _MESH_DEVICES_OVERRIDE
    raw = os.environ.get("PILOSA_DEVICE_MESH_DEVICES", "")
    if raw:
        try:
            v = int(raw)
            if v >= 1:
                return v
        except ValueError:
            pass
    return _parse_mesh_shape(os.environ.get("PILOSA_TPU_MESH_SHAPE", ""))


@functools.lru_cache(maxsize=16)
def _participating_devices(cap: int | None, n_local: int) -> tuple:
    """The device tuple for slice placement under a device-count cap —
    cached so the per-slice hot paths don't re-derive it."""
    n = n_local if cap is None else min(n_local, cap)
    return tuple(jax.local_devices()[:n])


def participating_devices() -> tuple:
    return _participating_devices(_mesh_devices_cap(), len(jax.local_devices()))


def mesh_device_count() -> int:
    """Local devices participating in slice placement and the slices
    mesh.  The ``[device] mesh-devices`` config (env
    ``PILOSA_DEVICE_MESH_DEVICES``; 0 = all visible, 1 = force
    single-device) caps it, as does the legacy ``tpu.mesh-shape``
    (``PILOSA_TPU_MESH_SHAPE``, e.g. "4" or "4x2" — the product of the
    factors); default all local devices.  With >1 participating device
    the mesh-sharded data plane engages BY DEFAULT
    (parallel/mesh.default_slices_mesh)."""
    return len(participating_devices())


def home_device(slice_i: int):
    """The device that owns a slice's fragment planes: ``slice mod
    n_devices`` — the in-host analog of the reference's slice->node
    placement (reference: cluster.go:202-216).  Lives here (not in
    parallel/) so the storage layer can pin planes without pulling in
    the mesh/planner machinery; parallel/mesh.py builds its sharded
    batches around the same mapping."""
    devs = participating_devices()
    return devs[slice_i % len(devs)]


# ---------------------------------------------------------------------------
# Per-plane-row container formats (the on-device roaring analog,
# ROADMAP item 2).  A sparse-tier row is encoded at write time into
# the cheapest of three layouts — mirroring the reference's
# dense-bitmap / sorted-array / run containers, selected by density
# (reference: roaring.go container conversion thresholds):
#
#   FMT_DENSE   uint32[WORDS_PER_SLICE] words           128 KiB always
#   FMT_SPARSE  sorted uint32 positions                 4 B / position
#   FMT_RLE     sorted (start, end) uint32 runs         8 B / run
#
# Sparse and RLE payloads are sentinel-padded (FMT_SENTINEL, which is
# > any slice position) up to their pow2 payload bucket so compiled
# programs key on a bounded bucket grid, never on raw cardinality.
# The fused kernels consume the payloads DIRECTLY (membership_* below
# gather against the compressed layout); dense expansion exists only
# as a transient for paths that must stack whole rows.
# ---------------------------------------------------------------------------

FMT_DENSE = 0
FMT_SPARSE = 1
FMT_RLE = 2
FMT_NAMES = {FMT_DENSE: "dense", FMT_SPARSE: "sparse", FMT_RLE: "rle"}

# Padding sentinel: all-ones is > any real position (< SLICE_WIDTH =
# 2^20) and sorts after every real payload entry.
FMT_SENTINEL = 0xFFFFFFFF

# Floor of the payload pow2 bucket grid (64 positions = 256 B, 64 runs
# = 512 B): tiny rows share one bucket instead of spraying compiles.
PAYLOAD_BUCKET_FLOOR = 64

# ``[device] plane-format``: "auto" selects per row by encoded bytes,
# "dense" disables compression (the contrast arm and the escape hatch).
# Set by Server.open from config; module-level like scatter.ENABLED so
# fragments see it without per-fragment plumbing.
PLANE_FORMAT = "auto"

# Per-row encoded-size caps ([device] plane-sparse-max-bytes /
# plane-rle-max-bytes): a format is eligible only while its BUCKETED
# payload fits the cap — the roaring "array container only below 4096
# entries" rule, expressed in bytes.  Default half a dense row, so any
# compressed row is at least a 2x save.
SPARSE_MAX_BYTES = 65536
RLE_MAX_BYTES = 65536


def configure_plane_format(
    mode: str | None = None,
    sparse_max_bytes: int | None = None,
    rle_max_bytes: int | None = None,
) -> None:
    """Apply ``[device] plane-format`` / threshold config process-wide
    (Server.open; tests and the sparse bench flip it for contrast
    arms).  Selection is write-time only: already-encoded device
    payloads keep their format until invalidated."""
    global PLANE_FORMAT, SPARSE_MAX_BYTES, RLE_MAX_BYTES
    if mode is not None:
        if mode not in ("auto", "dense"):
            raise ValueError(f"unknown plane-format {mode!r}")
        PLANE_FORMAT = mode
    if sparse_max_bytes is not None:
        SPARSE_MAX_BYTES = max(0, int(sparse_max_bytes))
    if rle_max_bytes is not None:
        RLE_MAX_BYTES = max(0, int(rle_max_bytes))


def payload_bucket(n: int) -> int:
    """Pow2 payload-length bucket (entries, not bytes) with the shared
    floor — the container-length shape class compiled programs key on."""
    return pow2_bucket(n, PAYLOAD_BUCKET_FLOOR)


def np_positions_to_runs(offsets: np.ndarray) -> np.ndarray:
    """Sorted positions -> (R, 2) uint32 half-open maximal runs."""
    o = np.asarray(offsets, dtype=np.uint32)
    if len(o) == 0:
        return np.zeros((0, 2), dtype=np.uint32)
    brk = np.nonzero(np.diff(o) != 1)[0]
    starts = o[np.concatenate(([0], brk + 1))]
    ends = o[np.concatenate((brk, [len(o) - 1]))].astype(np.uint64) + 1
    return np.stack([starts, ends.astype(np.uint32)], axis=1)


def encode_row(offsets: np.ndarray) -> tuple[int, np.ndarray, int]:
    """Write-time format selection for one sparse-tier row: encode the
    sorted in-slice positions into the cheapest eligible container and
    return ``(fmt, payload, encoded_nbytes)``.  Deterministic: minimum
    bucketed bytes wins, ties broken toward the lower format tag
    (dense < sparse < rle)."""
    offs = np.asarray(offsets, dtype=np.uint32)
    card = len(offs)
    dense_b = WORDS_PER_SLICE * 4
    cands = [(dense_b, FMT_DENSE)]
    if PLANE_FORMAT != "dense":
        sparse_b = 4 * payload_bucket(card)
        if sparse_b < dense_b and sparse_b <= SPARSE_MAX_BYTES:
            cands.append((sparse_b, FMT_SPARSE))
        runs = np_positions_to_runs(offs)
        rle_b = 8 * payload_bucket(len(runs))
        if rle_b < dense_b and rle_b <= RLE_MAX_BYTES:
            cands.append((rle_b, FMT_RLE))
    nbytes, fmt = min(cands)
    if fmt == FMT_SPARSE:
        payload = np.full(payload_bucket(card), FMT_SENTINEL, dtype=np.uint32)
        payload[:card] = offs
    elif fmt == FMT_RLE:
        runs = np_positions_to_runs(offs)
        payload = np.full(
            (payload_bucket(len(runs)), 2), FMT_SENTINEL, dtype=np.uint32
        )
        payload[: len(runs)] = runs
    else:
        payload = np_columns_to_row(offs)
    return fmt, payload, nbytes


def decode_payload(fmt: int, payload: np.ndarray) -> np.ndarray:
    """Host inverse of encode_row: any container payload -> dense row
    words (the byte-identity oracle for the codec tests)."""
    if fmt == FMT_DENSE:
        return np.asarray(payload, dtype=np.uint32)
    if fmt == FMT_SPARSE:
        p = np.asarray(payload, dtype=np.uint32)
        return np_columns_to_row(p[p != np.uint32(FMT_SENTINEL)])
    if fmt == FMT_RLE:
        p = np.asarray(payload, dtype=np.uint32).reshape(-1, 2)
        real = p[p[:, 0] != np.uint32(FMT_SENTINEL)]
        if len(real) == 0:
            return empty_row()
        pos = np.concatenate(
            [np.arange(s, e, dtype=np.uint32) for s, e in real]
        )
        return np_columns_to_row(pos)
    raise ValueError(f"unknown container format {fmt!r}")


# --- format-aware membership kernels ---------------------------------------
# Each takes one row's payload plus a sentinel-padded uint32 position
# vector and answers "is position p set?" per lane, reading only the
# compressed layout.  Sentinel lanes may answer garbage (the sparse
# kernel answers True: sentinel == sentinel pad); callers mask invalid
# lanes before reducing.  These are traced inside plan's anchored
# programs (vmapped over the slice axis), never jitted standalone.


def membership_dense(row, pos):
    w = jnp.minimum(
        pos >> jnp.uint32(5), jnp.uint32(WORDS_PER_SLICE - 1)
    ).astype(jnp.int32)
    return ((row[w] >> (pos & jnp.uint32(31))) & jnp.uint32(1)).astype(bool)


def membership_sparse(payload, pos):
    i = jnp.searchsorted(payload, pos)
    i = jnp.minimum(i, payload.shape[0] - 1)
    return payload[i] == pos


def membership_rle(payload, pos):
    starts = payload[:, 0]
    i = jnp.searchsorted(starts, pos, side="right").astype(jnp.int32) - 1
    ic = jnp.maximum(i, 0)
    return (i >= 0) & (pos < payload[ic, 1])


# --- transient dense expansion ---------------------------------------------
# For paths that must stack whole rows (the mesh gather path batches
# device_row results into dense leaf stacks), a resident compressed
# payload expands on device in one jitted scatter; the expansion is
# NEVER cached — the pool holds only the payload bytes.  Compiles key
# on the payload bucket (bounded grid, see program_cache_bounds).


@jax.jit
def _expand_sparse_xla(payload):
    idx = (payload >> jnp.uint32(5)).astype(jnp.int32)
    masks = jnp.uint32(1) << (payload & jnp.uint32(31))
    # Positions are unique, so per-word masks have disjoint bits and
    # scatter-add equals scatter-or; sentinel lanes index past the row
    # and drop.
    return jnp.zeros(WORDS_PER_SLICE, dtype=jnp.uint32).at[idx].add(
        masks, mode="drop"
    )


def _rle_lowmask(n):
    """uint32 mask of the low ``n`` bits, n in [0, 32]."""
    n32 = n.astype(jnp.uint32)
    return jnp.where(
        n32 >= jnp.uint32(32),
        jnp.uint32(0xFFFFFFFF),
        (jnp.uint32(1) << n32) - jnp.uint32(1),
    )


@jax.jit
def _expand_rle_xla(payload):
    s = payload[:, 0]
    e = payload[:, 1]
    w0 = (s >> jnp.uint32(5)).astype(jnp.int32)
    wl = ((e - jnp.uint32(1)) >> jnp.uint32(5)).astype(jnp.int32)
    b0 = s & jnp.uint32(31)
    bl = (e - jnp.uint32(1)) & jnp.uint32(31)
    same = w0 == wl
    # Boundary-word masks; runs are disjoint and maximal so masks
    # landing in a shared word have disjoint bits (add == or).
    # Sentinel runs (start == end == FMT_SENTINEL) produce zero masks
    # and out-of-range indices, which drop.
    m0 = _rle_lowmask(
        jnp.where(same, bl + jnp.uint32(1), jnp.uint32(32))
    ) & ~_rle_lowmask(b0)
    ml = jnp.where(same, jnp.uint32(0), _rle_lowmask(bl + jnp.uint32(1)))
    row = jnp.zeros(WORDS_PER_SLICE, dtype=jnp.uint32)
    row = row.at[w0].add(m0, mode="drop")
    row = row.at[wl].add(ml, mode="drop")
    # Interior full words via a +1/-1 difference array over word index.
    has_interior = (wl > w0 + 1).astype(jnp.int32)
    d = jnp.zeros(WORDS_PER_SLICE + 1, dtype=jnp.int32)
    d = d.at[w0 + 1].add(has_interior, mode="drop")
    d = d.at[wl].add(-has_interior, mode="drop")
    cover = jnp.cumsum(d)[:WORDS_PER_SLICE] > 0
    return row | jnp.where(cover, jnp.uint32(0xFFFFFFFF), jnp.uint32(0))


def expand_payload(fmt: int, payload):
    """Transient dense expansion of a device-resident compressed
    payload (mesh gather path).  FMT_DENSE payloads pass through."""
    if fmt == FMT_DENSE:
        return payload
    _note_shape(expand_payload=int(payload.shape[0]))
    if fmt == FMT_SPARSE:
        return _expand_sparse_xla(payload)
    if fmt == FMT_RLE:
        return _expand_rle_xla(payload)
    raise ValueError(f"unknown container format {fmt!r}")


@jax.jit
def _count_xla(words):
    return _popcount_sum(words)


def count(words):
    """Popcount of a row/plane (reference: popcntSliceAsm)."""
    return _count_xla(words)


@functools.partial(jax.jit, static_argnames=("op",))
def _fused_count_xla(a, b, op):
    if op == "and":
        return _popcount_sum(a & b)
    if op == "or":
        return _popcount_sum(a | b)
    if op == "xor":
        return _popcount_sum(a ^ b)
    if op == "andnot":
        return _popcount_sum(a & ~b)
    raise ValueError(f"unknown fused-count op {op!r}")


def count_and(a, b):
    """|a AND b| without materializing (reference: intersectionCount*,
    roaring/roaring.go:1259-1347, popcntAndSliceAsm)."""
    return _fused_count_xla(a, b, "and")


def count_or(a, b):
    return _fused_count_xla(a, b, "or")


def count_xor(a, b):
    return _fused_count_xla(a, b, "xor")


def count_andnot(a, b):
    """|a AND NOT b| (reference: popcntMaskSliceAsm / differenceCount)."""
    return _fused_count_xla(a, b, "andnot")


# Materializing set algebra (reference: roaring/roaring.go:345-474 dispatch,
# 1349-1716 kernels) — a single vector op on the dense plane.


@jax.jit
def and_(a, b):
    return a & b


@jax.jit
def or_(a, b):
    return a | b


@jax.jit
def xor(a, b):
    return a ^ b


@jax.jit
def andnot(a, b):
    return a & ~b


def _range_mask(n: int, start, end) -> jnp.ndarray:
    """uint32[n] word masks selecting bit positions in [start, end).

    Built word-by-word (not per-bit) so XLA fuses it into the consuming
    bitwise op.  start/end fit comfortably in int32 (SLICE_WIDTH = 2^20).
    """
    lo = jnp.arange(n, dtype=jnp.int32) * WORD_BITS
    s = jnp.clip(start - lo, 0, WORD_BITS).astype(jnp.uint32)
    e = jnp.clip(end - lo, 0, WORD_BITS).astype(jnp.uint32)
    width = jnp.maximum(e.astype(jnp.int32) - s.astype(jnp.int32), 0).astype(jnp.uint32)
    full = jnp.uint32(0xFFFFFFFF)
    base = jnp.where(width == 32, full, (jnp.uint32(1) << width) - jnp.uint32(1))
    return (base << s).astype(jnp.uint32)


@jax.jit
def flip_range(words, start, end):
    """Negate bits in [start, end) of a flat word array (reference:
    roaring.Bitmap.Flip, roaring/roaring.go:708-734)."""
    return words ^ _range_mask(words.shape[-1], start, end)


@jax.jit
def count_range(words, start, end):
    """Count set bits with positions in [start, end) (reference:
    roaring.Bitmap.CountRange, roaring/roaring.go:195-249)."""
    return _popcount_sum(words & _range_mask(words.shape[-1], start, end))


@jax.jit
def row_counts(plane):
    """Per-row popcounts of a plane -> int32[rows] (rebuilds the ranked
    cache after imports; reference: fragment.go:244-282 openCache recount)."""
    return jnp.sum(jax.lax.population_count(plane).astype(jnp.int32), axis=-1)


@jax.jit
def _top_counts_xla(plane, src_row):
    return jnp.sum(
        jax.lax.population_count(plane & src_row[None, :]).astype(jnp.int32), axis=-1
    )


# Largest bucketed dimension each scorer family has seen — the inputs to
# the hard cardinality bounds (exec/plan.program_cache_bounds): every
# dimension below is pow2-bucketed by the callers, so a family's compiled
# entry count can never exceed the product of its dimensions' class
# counts.  Plain dict writes (no lock): racing writers both store valid
# maxima and the bound is re-derived per read.
_SHAPE_HIGHWATER: dict[str, int] = {}


def _note_shape(**dims: int) -> None:
    for k, v in dims.items():
        if v > _SHAPE_HIGHWATER.get(k, 0):
            _SHAPE_HIGHWATER[k] = v


def shape_highwater() -> dict[str, int]:
    return dict(_SHAPE_HIGHWATER)


def word_classes() -> int:
    """The row-width classes of the plane mirrors the plane-shaped
    program families have been called with (``row_words``: pow2, floor
    MIN_ROW_WORDS): a factor of each such family's bound."""
    return bucket_classes(
        max(_SHAPE_HIGHWATER.get("plane_words", MIN_ROW_WORDS), MIN_ROW_WORDS),
        MIN_ROW_WORDS,
    )


def top_counts(plane, src_row):
    """Per-row |row AND src| -> int32[rows]: the batched TopN(Src=...) scorer.

    The reference prunes candidates sequentially with cache-threshold early
    termination (reference: fragment.go:601-627); on TPU we instead score
    every row in one fused batched kernel and select on the host — same
    results, hardware-shaped loop structure.
    """
    _note_shape(top_rows=int(plane.shape[0]), plane_words=int(plane.shape[1]))
    return _top_counts_xla(plane, src_row)


@jax.jit
def _score_planes_self_src(planes, slots, src_slots):
    outs = []
    for f in range(len(planes)):
        rows = planes[f][slots[f]]
        src = planes[f][src_slots[f]]
        outs.append(
            jnp.sum(
                jax.lax.population_count(rows & src[None, :]).astype(jnp.int32),
                axis=-1,
            )
        )
    return jnp.stack(outs)


# The scorer kernel's lanes, the words of a plane it counts at a time
# and the most rows it takes: a [64, 512] block is 32 of the core's 64
# vector registers, and two whole planes of 64 rows x 32,768 words are
# 16 MiB of its fast memory.
SCORE_LANES = 128
SCORE_CHUNK_WORDS = 512
SCORE_KERNEL_ROWS = 64


def kernel_scores(plane_shape: tuple, n_slots: int) -> bool:
    """Whether the TPU scores planes of ``plane_shape`` by the kernel
    (``_score_planes_kernel``): planes no taller than their candidate
    slots (the slots are then the plane's rows, padded by repeats, and
    the plane is read whole either way), of whole register tiles.  Any
    other plane gathers its candidates (``_score_planes_self_src``)."""
    rows, words = plane_shape
    return (
        rows <= min(n_slots, SCORE_KERNEL_ROWS)
        and rows % 8 == 0
        and words % SCORE_LANES == 0
        and words % min(words, SCORE_CHUNK_WORDS) == 0
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def _score_planes_kernel(planes, slots, src_slots, interpret=False):
    """The self-src scorer as ONE kernel a launch: each member's plane is
    copied whole into fast memory, the next member's copy running while
    this one is counted, and every row's ``|row AND src|`` is summed
    from the src row where it lies in the same buffer.  The candidates'
    counts are picked after, by ``slots``.  Same bytes from HBM as the
    fused XLA program, in a handful of device operations a launch where
    that one has four a member: what a profile of the busy TopN cell
    holds, and what reading it costs, is per operation."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    members = len(planes)
    rows, words = planes[0].shape
    chunk = min(SCORE_CHUNK_WORDS, words)

    def kernel(src_ref, *refs):
        mirrors, out_ref, buf, sem = refs[:members], refs[members], refs[-2], refs[-1]

        def copy(f):
            return pltpu.make_async_copy(mirrors[f], buf.at[f % 2], sem.at[f % 2])

        copy(0).start()
        for f in range(members):
            if f + 1 < members:
                copy(f + 1).start()
            copy(f).wait()
            at, src = f % 2, src_ref[f]

            def count(c, acc, at=at, src=src):
                cols = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
                ones = jax.lax.population_count(
                    buf[at, :, cols] & buf[at, pl.ds(src, 1), cols]
                ).astype(jnp.int32)
                for k in range(0, chunk, SCORE_LANES):
                    acc = acc + ones[:, k:k + SCORE_LANES]
                return acc

            out_ref[f] = jax.lax.fori_loop(
                0, words // chunk, count, jnp.zeros((rows, SCORE_LANES), jnp.int32)
            )

    lanes = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((members, rows, SCORE_LANES), jnp.int32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * members,
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((2, rows, words), jnp.uint32),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=4 * rows * (2 * words + members * SCORE_LANES) + (8 << 20)
        ),
        interpret=interpret,
    )(src_slots, *planes)
    counts = jnp.sum(lanes, axis=-1)
    return jnp.take_along_axis(counts, slots, axis=1, mode="clip")


def self_src_scorer(platform: str, plane_shape: tuple, n_slots: int):
    """The self-src scorer program for planes of ``plane_shape`` and
    ``n_slots`` candidate slots on ``platform``."""
    if platform == "tpu" and kernel_scores(plane_shape, n_slots):
        return _score_planes_kernel
    return _score_planes_self_src


@jax.jit
def _score_planes_host_src(planes, slots, srcs):
    outs = []
    for f in range(len(planes)):
        rows = planes[f][slots[f]]
        outs.append(
            jnp.sum(
                jax.lax.population_count(rows & srcs[f][None, :]).astype(
                    jnp.int32
                ),
                axis=-1,
            )
        )
    return jnp.stack(outs)


SCORE_PROGRAMS = (_score_planes_self_src, _score_planes_kernel, _score_planes_host_src)


# Fragments one compiled scorer program takes.  The program is unrolled
# once per member (each plane mirror is an operand of its own: stacking
# them would copy the planes), so its compile time grows with the
# member count: 65.7 s at the 1024 members one 954-slice index once
# passed (my chip run, PR 21).  Larger groups run as ceil(n / SCORE_GROUP)
# launches of the SAME program, so neither the jit key nor the compile
# time depends on how many slices an index has.  The value is from
# tools/topn_scorer_sweep.py on the chip (PERF.md, PR 29; 954 planes of
# 64 rows): the first call costs 65 ms a member (2.2 s at 32, 4.2 s at
# 64, 7.4 s at 128), a launch 0.46 ms of dispatch whatever its size, and
# a whole answer 16.5 / 13.3 / 18.0 ms at 32 / 64 / 128: at 64 the 15
# dispatches hide behind the 13 ms of streaming.
SCORE_GROUP = 64


def score_group_bucket(n_frags: int) -> int:
    """Members of the program that scores ``n_frags`` fragments: the
    pow2 class of a small group, never more than SCORE_GROUP."""
    return min(pow2_bucket(n_frags), SCORE_GROUP)


# Program shapes the scorer, the leaf-batch gather and the in-place
# aggregate have been called with: a shape's first call traces and
# compiles (or loads) its program, one thread at a time, and its caller
# is told so (``_first_call``).
_SCORE_SEEN: set = set()
_FIRST_CALL_MU = threading.Lock()


def _first_call(fn, shape: tuple, first_call, *args):
    """``fn(*args)``; a program shape's first call is made one thread
    at a time (eight requests that arrive together would otherwise each
    trace and compile the same program) and told to ``first_call``:
    the shape as text, its wall-clock start and how long it took."""
    if shape in _SCORE_SEEN:
        return fn(*args)
    with _FIRST_CALL_MU:
        if shape in _SCORE_SEEN:
            return fn(*args)
        start, t0 = time.time(), time.monotonic()
        out = fn(*args)
        _SCORE_SEEN.add(shape)
    if first_call is not None:
        first_call(str(shape), start, (time.monotonic() - t0) * 1e3)
    return out


def score_planes(planes, slots, src_slots=None, srcs=None, first_call=None) -> list:
    """Cross-fragment TopN scorer that reads STRAIGHT from the
    fragments' HBM-resident plane mirrors — no stacked candidate copy
    ever materializes (a stacked batch doubled the candidate rows'
    device footprint and tripped OOM at 100 slices x 256 candidates).

    ``planes``: sequence of uint32[plane_rows, words] device mirror
    SNAPSHOTS, one per fragment; ``slots``: int32[n_frag, rows]
    candidate slot indices (one small transfer a launch); the src is
    either ``src_slots`` int32[n_frag] — the src row's slot in the SAME
    plane (the common TopN(Bitmap(frame=f), frame=f) shape; zero src
    bytes host->device, and no extra leaf shapes enter the jit key) — or
    ``srcs`` uint32[n_frag, words] host-snapshot rows.  Gathers fuse
    into the popcount reduce, so each candidate row is read once; on
    the TPU a plane no taller than its slots is read whole by one
    kernel a launch (``kernel_scores``).

    The fragments are scored ``score_group_bucket(n_frag)`` at a time:
    every launch is dispatched without waiting and the device arrays
    are returned as a list, int32[bucket, rows] each, in fragment order
    (the last launch is padded by repeating its last member; surplus
    rows are simply not read back).  The caller fetches them in ONE
    device->host round trip.  ``first_call(shape, start, ms)`` is told
    of a program shape's first call, the one that compiles: its shape
    as text, its wall-clock start and how long it took.

    Every dimension of the jit key is pow2-bucketed and bounded —
    members (here), plane rows (pad_rows at plane allocation),
    candidate slots (pad_rows at prepare) — so the compiled-program
    count is the product of the classes, whatever the number of
    fragments and however many distinct fragment shapes the schema
    churns through.
    """
    n = len(planes)
    bucket = score_group_bucket(n)
    _note_shape(
        score_frags=bucket,
        score_rows=max(int(p.shape[0]) for p in planes),
        score_slots=int(slots.shape[-1]),
        plane_words=int(planes[0].shape[1]),
    )
    devs = getattr(planes[0], "devices", None)
    devices = devs() if callable(devs) else ()
    platform = next(iter(devices)).platform if devices else "cpu"
    fn, src = (
        (self_src_scorer(platform, tuple(planes[0].shape), int(slots.shape[-1])), src_slots)
        if srcs is None
        else (_score_planes_host_src, srcs)
    )
    shape = (
        "self" if srcs is None else "host",
        bucket,
        tuple(planes[0].shape),
        int(slots.shape[-1]),
        str(sorted(map(str, devices))) if devices else "",
    )
    outs = []
    # The kernel, as the aggregate's DMA kernel, carries its source
    # locations into the compile cache's key: without whole call stacks
    # in them the prewarm and a request lower one program, not two.
    from jax._src import config as jax_config

    with jax_config.include_full_tracebacks_in_locations(False):
        for lo in range(0, n, bucket):
            idx = np.minimum(np.arange(lo, lo + bucket), n - 1)
            group = tuple(planes[i] for i in idx)
            outs.append(
                _first_call(fn, shape, first_call, group, slots[idx], src[idx])
            )
    return outs


# Hits a walked score hands back compacted (``score_rows``): the rows a
# text keeps are few (a screen's answer is tens to thousands of pairs)
# beside the rows it scores (millions), so what crosses to the host is
# this many (slot, shared bits) pairs and a count, not a vector as long
# as the plane.  A text that keeps more fetches the vector as well.
ROW_HITS = 1 << 14

# Kept rows one step of the hand-back locates.  A step costs the device
# by the rows it locates, whatever the plane's size, so the steps taken
# follow the rows a text keeps and not ROW_HITS: a median screen answer
# (tens of pairs) takes one, its largest (a few thousand) four.  At
# [2^21, 128] a step is 0.042 ms beside a walk of 1.49 (my chip run,
# PR 37: 256 / 512 / 2,048 read 0.016 / 0.025 / 0.074 a step, and an
# answer of 3,300 rows 1.71 / 1.66 / 1.64 / 1.64 ms at 256 ... 2,048);
# the program's compile time grows with it (1.6 s at 256, 4.0 at 1,024,
# 14 at 2,048, 45 at 4,096).
ROW_STEP = 1 << 10


def row_step(k: int) -> int:
    """Kept rows a step of the hand-back locates when a launch hands
    back ``k`` at most (``len(slots)`` of ``score_rows``)."""
    return min(ROW_STEP, k)


@jax.jit
def _score_rows_xla(plane, cnts, q):
    src = jax.lax.dynamic_index_in_dim(plane, q[0], axis=0, keepdims=False)
    c = jnp.sum(
        jax.lax.population_count(plane & src[None, :]).astype(jnp.int32), axis=-1
    )
    s, t, m = q[1], q[2], q[3]
    # upstream's rules in integers (each side under 2^31 while a row has
    # at most 2^20 columns): the window on cached counts
    # cnt > s*t/100 and cnt < s*100/t, and ceil(100c / (cnt+s-c)) > t
    windowed = (100 * cnts > s * t) & (cnts * t < 100 * s)
    similar = 100 * c > t * (cnts + s - c)
    keep = (cnts > 0) & (c > 0) & jnp.where(
        t > 0, windowed & similar, (cnts >= m) & (c >= m)
    )
    hits = jnp.sum(keep.astype(jnp.int32))
    # The slots of the first k kept rows, ``step`` of them a trip of a
    # loop that runs ceil(min(hits, k) / step) times, each in two stages
    # of bounded size: which block of ``lanes`` rows holds the j-th kept
    # row, by counting the blocks whose running count is under j (a fused
    # compare-and-count over [step, blocks]: a binary search's probes are
    # serial loads from HBM, 0.145 ms a step where this takes 0.042); then
    # which row of that block, by a prefix sum along it.  All k = 16,384
    # at once, by binary search, took 2.4 ms an answer of twenty pairs
    # beside a walk of 1.5 (my chip runs, PR 36, PR 37).
    rows = plane.shape[0]
    k, lanes = min(ROW_HITS, rows), min(128, rows)
    step = row_step(k)
    blocks = keep.reshape(rows // lanes, lanes).astype(jnp.int32)
    ends = jnp.cumsum(blocks.sum(axis=1))
    first = jnp.arange(1, step + 1, dtype=jnp.int32)

    def locate(i, out):
        at, shared = out
        j = i * step + first
        b = jnp.minimum(
            jnp.searchsorted(ends, j, method="compare_all"), rows // lanes - 1
        )
        mine = blocks[b]
        need = j - (ends[b] - mine.sum(axis=1))
        lane = jnp.sum(jnp.cumsum(mine, axis=1) < need[:, None], axis=1)
        mine_at = (b * lanes + jnp.minimum(lane, lanes - 1)).astype(jnp.int32)
        return (
            jax.lax.dynamic_update_slice(at, mine_at, (i * step,)),
            jax.lax.dynamic_update_slice(shared, c[mine_at], (i * step,)),
        )

    # (k is a whole number of steps: a plane's row class and the two
    # sizes are powers of two)
    zeros = jnp.zeros(k, dtype=jnp.int32)
    at, shared = jax.lax.fori_loop(
        0, (jnp.minimum(hits, k) + step - 1) // step, locate, (zeros, zeros)
    )
    return hits, at, shared, jnp.where(keep, c, 0)


def score_rows(plane, cnts, src_slot: int, src_count: int, tanimoto: int,
               min_threshold: int, first_call=None):
    """The TopN scorer of ONE fragment whose candidates are every row
    of its plane (rows: molecules, say): ``plane`` uint32[rows, words],
    the mirror snapshot at whatever width the fragment keeps, is walked
    once — AND with row ``src_slot`` of itself, popcount, a lane reduce
    a row — and filtered where the counts are: ``cnts`` int32[rows], the
    ranked cache's count of the row in each slot (0: no candidate),
    resident beside the plane, gives the tanimoto count window and the
    similarity rule (or, without ``tanimoto``, ``min_threshold``), as
    ``Fragment._filter_arrays`` and ``top_score_arrays`` apply them.

    Dispatched without waiting; returns device arrays ``(hits, slots,
    shared, every)``: how many rows are kept, the slots of the first
    ROW_HITS of them in slot order with their shared-bit counts, and
    int32[rows] with a kept row's shared bits and 0 elsewhere, which a
    caller fetches only when ``hits`` is over ROW_HITS; entries of
    ``slots`` and ``shared`` past ``hits`` are unspecified.  Beyond the
    walk, the device's time follows the rows kept: they are located
    ``row_step`` at a time, so a text that keeps a handful pays one
    step and not the ROW_HITS serial probes a round that locating them
    all at once cost every answer (PERF.md, PR 36, PR 37).  The text's
    numbers and ``hits`` are operands: the jit key is the plane's shape
    and its device, so the programs are the row classes x the word
    classes, whatever the row count, the src or the threshold."""
    _note_shape(walk_rows=int(plane.shape[0]), plane_words=int(plane.shape[1]))
    dev = device_of(plane)
    shape = ("rows", tuple(plane.shape), str(dev))
    q = np.asarray([src_slot, src_count, tanimoto, min_threshold], dtype=np.int32)
    return _first_call(_score_rows_xla, shape, first_call, plane, cnts, q)


@jax.jit
def _gather_planes_xla(planes, table, i):
    sl = table[i]
    rows = [
        jax.lax.dynamic_slice_in_dim(
            planes[f], jnp.maximum(sl[f, j], 0), 1, axis=0
        )
        for f in range(len(planes))
        for j in range(sl.shape[1])
    ]
    out = jnp.concatenate(rows).reshape(len(planes), sl.shape[1], -1)
    return jnp.where((sl >= 0)[:, :, None], out, jnp.uint32(0))


@functools.partial(jax.jit, donate_argnums=0)
def _place_rows_xla(block, rows, row0, col0):
    return jax.lax.dynamic_update_slice(block, rows, (row0, col0, 0))


@functools.partial(jax.jit, donate_argnums=0)
def _place_const_xla(block, row, n, col):
    live = jnp.arange(block.shape[0], dtype=jnp.int32)[:, None, None] < n
    column = jnp.where(live, row[None, None, :], jnp.uint32(0))
    return jax.lax.dynamic_update_slice(block, column, (0, col, 0))


GATHER_PROGRAMS = (_gather_planes_xla, _place_rows_xla, _place_const_xla)

# Launches one slot table serves (GATHER_TABLE x members bucket members):
# the table's shape is in the gather's jit key, so it is one size
# whatever the number of fragments, and a larger set takes more tables.
GATHER_TABLE = 16

@functools.lru_cache(maxsize=8192)
def _device_int(value: int, dev):
    """``value`` as an int32 scalar resident on ``dev``.  A launch's row
    and column offsets are operands; handed over as host numbers each is
    a host->device transfer of its own, and four of them a launch were
    most of what a launch cost the host (PERF.md, PR 31)."""
    return jax.device_put(np.int32(value), dev)


def device_of(arr):
    return next(iter(arr.devices()))


def gather_planes(planes, slots, first_call=None):
    """Leaf rows of many fragments, gathered on the device from their
    HBM-resident plane mirrors: the leaf batch of a query, with no host
    copy of a row and no transfer but the slots.

    ``planes``: sequence of uint32[plane_rows, words] mirror SNAPSHOTS
    of one shape on ONE device, a member each; ``slots``: int[n, k], the
    slot of each of a member's ``k`` rows in its plane, negative for a
    row the fragment does not hold: that row gathers zeros (a full
    plane has no spare zero slot to point at).

    As ``score_planes``: ``score_group_bucket(n)`` members a launch,
    every launch dispatched without waiting, the last one padded by
    repeating its last member with no row valid, so its surplus rows
    are zeros.  The slots go to the device once, as a table of
    GATHER_TABLE launches, and a launch is told its place in it by a
    resident scalar: its operands cross no host boundary.  Yields the
    launches' device arrays, uint32[bucket, k, words] each, in member
    order, each as it is dispatched: a caller that consumes one before
    asking for the next keeps one output alive, not all.  The jit key
    is (members bucket, plane shape, k, device) — never the expression,
    never the number of fragments."""
    n = len(planes)
    bucket = score_group_bucket(n)
    slots = np.asarray(slots, dtype=np.int32).reshape(n, -1)
    k = int(slots.shape[1])
    _note_shape(
        gather_frags=bucket,
        gather_rows=int(planes[0].shape[0]),
        gather_leaves=k,
        plane_words=int(planes[0].shape[1]),
    )
    dev = device_of(planes[0])
    shape = ("gather", bucket, tuple(planes[0].shape), k, str(dev))
    per_table = GATHER_TABLE * bucket
    for t0 in range(0, n, per_table):
        table = np.full((per_table, k), -1, dtype=np.int32)
        table[: min(n - t0, per_table)] = slots[t0 : t0 + per_table]
        table = jax.device_put(table.reshape(GATHER_TABLE, bucket, k), dev)
        for i, lo in enumerate(range(t0, min(n, t0 + per_table), bucket)):
            group = tuple(planes[min(m, n - 1)] for m in range(lo, lo + bucket))
            yield _first_call(
                _gather_planes_xla, shape, first_call,
                group, table, _device_int(i, dev),
            )


def place_rows(block, rows, row0: int, col0: int = 0, first_call=None):
    """``block`` with ``rows`` (uint32[m, k, words], on the block's
    device) written at ``[row0 : row0 + m, col0 : col0 + k]``, in place:
    ``block`` is donated and must not be used again.  The caller keeps
    the write inside the block (XLA would shift a write that does not
    fit).  Keyed by the two shapes and the device; the offsets are
    operands."""
    _note_shape(place_rows=int(block.shape[0]), place_leaves=int(block.shape[1]))
    dev = device_of(block)
    shape = ("place", tuple(block.shape), tuple(rows.shape), str(dev))
    return _first_call(
        _place_rows_xla, shape, first_call,
        block, rows, _device_int(int(row0), dev), _device_int(int(col0), dev),
    )


def place_const(block, row, n: int, col: int, first_call=None):
    """``block`` with the one row ``row`` (uint32[words], host or
    device) written into column ``col`` of its first ``n`` members and
    zeros into that column of the rest, in place (``block`` is
    donated): a slice-invariant leaf, such as a BSI predicate row."""
    dev = device_of(block)
    shape = ("const", tuple(block.shape), str(dev))
    return _first_call(
        _place_const_xla, shape, first_call,
        block, row, _device_int(int(n), dev), _device_int(int(col), dev),
    )


class _LeafRows(list):
    """A member's leaf rows as the expression evaluator takes them: it
    indexes them by leaf, and asks their shape only for an empty fold."""

    shape = (0, WORDS_PER_SLICE)
    dtype = jnp.uint32


# Rows of one tile of a plane mirror on the chip: a DMA moves whole
# tiles, so a copy that starts inside a plane starts at a multiple of
# this and takes this many rows (ROW_BLOCK, the planes' own row class
# floor, is the same 8).
TILE_ROWS = 8


def _unit_rows(units, planes) -> list[int]:
    """Rows the DMA stage copies of each unit of a member: the whole
    plane of a ``"whole"`` unit (its shape is the operand's own), the
    TILE_ROWS around one row of a ``"tile"`` unit."""
    return [
        int(p.shape[0]) if unit == "whole" else TILE_ROWS
        for unit, p in zip(units, planes)
    ]


def _copy_units(units, planes, tiles, interpret: bool):
    """The DMA stage of the aggregate: uint32[members, R, words], for
    every member its units' rows one after another.  ``units``: a unit
    each ``"whole"`` — the member's whole plane of that unit — or
    ``"tile"`` — the TILE_ROWS rows of it that start at
    ``tiles[member, unit] * TILE_ROWS``; ``planes``: the mirrors,
    member-major, a unit each.  One kernel a launch whatever the
    members: every copy is started, then every copy is waited for, and
    no byte passes through the core."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_units = len(units)
    members = len(planes) // n_units
    rows = _unit_rows(units, planes)
    offsets = [sum(rows[:u]) for u in range(n_units)]

    def kernel(tiles_ref, *refs):
        mirrors, out_ref, sem = refs[:-2], refs[-2], refs[-1]
        copies = []
        for f in range(members):
            for u, unit in enumerate(units):
                src = mirrors[f * n_units + u]
                if unit == "tile":
                    first = pl.multiple_of(tiles_ref[f, u] * TILE_ROWS, TILE_ROWS)
                    src = src.at[pl.ds(first, TILE_ROWS), :]
                copy = pltpu.make_async_copy(
                    src, out_ref.at[f, pl.ds(offsets[u], rows[u]), :], sem
                )
                copy.start()
                copies.append(copy)
        for copy in copies:
            copy.wait()

    block = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(
            (members, sum(rows), WORDS_PER_SLICE), jnp.uint32
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * len(planes),
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        ),
        interpret=interpret,
    )(tiles, *planes)
    return block, offsets, rows


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _aggregate_planes_xla(evaluate, expr, cols, units, interpret, planes, table, i, preds):
    sl = table[i]
    held = sl >= 0
    at = jnp.maximum(sl, 0)
    # the column that says where a "tile" unit's rows start
    tile_col = {c[1]: c[2] for c in cols if c[0] == "row" and units[c[1]] == "tile"}
    tiles = jnp.stack(
        [
            at[:, tile_col[u]] // TILE_ROWS
            if u in tile_col
            else jnp.zeros(sl.shape[:1], jnp.int32)
            for u in range(len(units))
        ],
        axis=1,
    )
    # A plane narrower than a slice (``row_words``) is widened here, on
    # read: the DMA stage and the expression take full-width rows.  Of a
    # "tile" unit only the tile is (a narrow plane may hold millions of
    # rows); it then stands at tile 0 of its own eight rows.
    n_units = len(units)
    narrow = [int(p.shape[1]) < WORDS_PER_SLICE for p in planes[:n_units]]
    if any(narrow):
        planes = list(planes)
        for i, p in enumerate(planes):
            f, u = divmod(i, n_units)
            if not narrow[u]:
                continue
            if units[u] == "tile":
                p = jax.lax.dynamic_slice_in_dim(
                    p, tiles[f, u] * TILE_ROWS, TILE_ROWS, axis=0
                )
            planes[i] = jnp.pad(p, ((0, 0), (0, WORDS_PER_SLICE - p.shape[1])))
        tiles = jnp.where(
            jnp.asarray([n and unit == "tile" for n, unit in zip(narrow, units)]),
            0,
            tiles,
        )
    block, offsets, unit_rows = _copy_units(units, planes, tiles, interpret)
    # Every leaf row, picked out of the block by the member's own slot
    # (of a tile: the slot within it) in ONE gather a launch: where the
    # fragments keep a row is data, so neither a write nor another field
    # of the same shape is a new program.  (A gather a leaf is four times
    # the device operations a launch, 412 against 103, and a profile of
    # the cell's 10 s then holds millions: my chip run, PR 34.)
    where = [c for c in cols if c[0] == "row"]
    at_row = jnp.stack(
        [offsets[u] + at[:, k] % unit_rows[u] for _, u, k in where], axis=1
    )
    picked = jnp.take_along_axis(block, at_row[:, :, None], axis=1)
    rows = []  # a leaf: uint32[members, words], or what every member shares
    n = 0
    for col in cols:
        if col[0] == "pred":
            rows.append(preds[col[1]])
        elif col[0] == "row":
            rows.append(
                jnp.where(held[:, col[2]][:, None], picked[:, n], jnp.uint32(0))
            )
            n += 1
        else:
            rows.append(jnp.zeros((WORDS_PER_SLICE,), jnp.uint32))
    batched = [r for r in rows if r.ndim == 2]

    def one(*mine):
        mine = iter(mine)
        return evaluate(
            expr, _LeafRows(next(mine) if r.ndim == 2 else r for r in rows)
        )

    return jax.vmap(one)(*batched)


# Members one aggregate launch takes, at most: its DMA stage copies
# their units into one block inside the program (uint32[members, R,
# words]), which the expression then reads as ONE operand, so a launch
# is a hundred-odd device operations whatever its members.  (Its first
# form read every row where it lay, unrolled a member, and ran 29,000
# tiny operations an answer: 19 ms on the device for 3.8 ms of bytes,
# 21 s of compile a query template, and a profile of a 10 s window that
# did not return in 10 minutes.  PERF.md, PR 34.)  Larger sets run as
# ceil(n / members) launches of the same program.
AGG_GROUP = 16

# And the bytes of that block and of the rows picked out of it (both
# are alive while the gather runs), at most: up to here the chip's
# compiler keeps them in the core's fast memory (8 members x (56 + 44)
# rows, 100 MiB, it does; a block of 16 x 64 rows it left in HBM, where
# the copy took 2.8 times as long and reading a row out of it 12 times:
# my chip runs, PR 34).
AGG_BLOCK_BYTES = 112 << 20


def agg_members(n: int, rows: int) -> int:
    """Members of the program that takes ``n`` members of ``rows`` rows
    each (what the DMA stage copies of a member and the leaf rows picked
    out of that): the pow2 class of a small set, never more than
    AGG_GROUP, never over AGG_BLOCK_BYTES."""
    fit = AGG_BLOCK_BYTES // (rows * WORDS_PER_SLICE * 4)
    return max(1, min(pow2_bucket(n), AGG_GROUP, 1 << max(fit.bit_length() - 1, 0)))


# Words of a predicate row that travel to an aggregate launch: the
# magnitude bits of the deepest field (bsi.MAX_DEPTH) and the sign flag.
PRED_WORDS = 128

# The (expression, leaf layout, unit kinds) of the aggregate programs
# called so far — what the call's text alone decides: the bound of the
# family counts them, times the shape classes of what the fragments
# decide (exec/plan.program_cache_bounds).
_AGG_SEEN: set = set()


def aggregate_planes(evaluate, expr, cols, units, planes, slots, preds, first_call=None):
    """A BSI aggregate (or any expression that reduces inside itself)
    over many slices, computed from the fragments' HBM-resident plane
    mirrors by ONE program a launch: the mirrors are operands, a DMA
    stage copies each member's units side by side into a block that
    lives inside the program (``_copy_units``), and the expression,
    vmapped over the members, picks every leaf row out of it by slot.
    No leaf batch is assembled, cached or padded, and nothing of a launch
    outlives it but its vectors.

    ``evaluate(expr, leaves)`` is the expression evaluator (exec/plan's
    own, handed in: this module does not import the plan layer) and
    ``expr`` the decomposed tree; ``cols`` says, a leaf, where its row
    comes from: ``("row", unit, k)`` — the row at ``slots[:, k]`` of the
    member's plane of ``unit``; ``("pred", p)`` — ``preds[p]``, a packed
    predicate (``preds``: uint32[n, PRED_WORDS], one small transfer a
    call: a constant is data); ``("zero",)`` — a depth-bucket pad, a
    literal zero that the compiler folds away.  ``units``: what the DMA
    stage copies of a member, each ``"whole"`` (a plane most of whose
    rows are read: a field's) or ``"tile"`` (the tile of rows around one
    row: a Bitmap's).  All three come from the call's text alone.
    ``planes``: the members' mirror SNAPSHOTS, member-major, a unit each,
    all on ONE device and of one shape a unit; ``slots``: int[members,
    K], negative for a row the member's fragment does not hold (it reads
    as zeros): which rows a fragment keeps, and where, is data.

    As ``score_planes`` and ``gather_planes``: ``agg_members`` members a
    launch, the last padded by repeating its last member with no row
    held; the slots cross to the device once per GATHER_TABLE launches.
    Returns the launches' device arrays, int32[bucket, vector] each, in
    member order.  The jit key is (expression, cols, units, the units'
    plane shapes, members bucket, device): never the slice count, a
    constant, or what a fragment holds."""
    n_units = len(units)
    n = len(planes) // n_units
    slots = np.asarray(slots, dtype=np.int32).reshape(n, -1)
    k = int(slots.shape[1])
    bucket = agg_members(n, sum(_unit_rows(units, planes)) + k)
    _AGG_SEEN.add((expr, cols, units))
    _note_shape(
        agg_frags=bucket,
        agg_units=n_units,
        agg_rows=max(int(p.shape[0]) for p in planes[:n_units]),
        plane_words=max(int(p.shape[1]) for p in planes[:n_units]),
    )
    dev = device_of(planes[0])
    shape = (
        "agg", expr, cols, units,
        tuple(tuple(p.shape) for p in planes[:n_units]), bucket, str(dev),
    )
    interpret = dev.platform != "tpu"
    preds = jax.device_put(
        np.asarray(preds, dtype=np.uint32).reshape(-1, PRED_WORDS), dev
    )
    per_table = GATHER_TABLE * bucket
    outs = []
    # The DMA kernel travels inside the program as bytes that carry its
    # operations' source locations; with whole call stacks in them the
    # same kernel lowered under the prewarm and under a request is two
    # programs to the persistent compile cache (a restart then compiles
    # what the first boot cached: chip_smoke.py, PR 34).
    from jax._src import config as jax_config

    with jax_config.include_full_tracebacks_in_locations(False):
        for t0 in range(0, n, per_table):
            table = np.full((per_table, k), -1, dtype=np.int32)
            table[: min(n - t0, per_table)] = slots[t0 : t0 + per_table]
            table = jax.device_put(table.reshape(GATHER_TABLE, bucket, k), dev)
            for i, lo in enumerate(range(t0, min(n, t0 + per_table), bucket)):
                group = tuple(
                    planes[min(m, n - 1) * n_units + u]
                    for m in range(lo, lo + bucket)
                    for u in range(n_units)
                )
                outs.append(
                    _first_call(
                        _aggregate_planes_xla, shape, first_call,
                        evaluate, expr, cols, units, interpret,
                        group, table, _device_int(i, dev), preds,
                    )
                )
    return outs


@functools.partial(jax.jit, static_argnames=("k",))
def top_k(counts, k: int):
    """Top-k (count, rowID) by count descending — ties broken by smaller row
    id first, matching the reference's Pair sort (reference: cache.go:316-330).
    """
    kk = min(k, counts.shape[0])
    # lax.top_k breaks ties toward the lower index, which matches the
    # reference's Pair ordering (count desc, then smaller row id).
    topc, topidx = jax.lax.top_k(counts, kk)
    return topc, topidx


def batch_rows(rows: list[np.ndarray]) -> np.ndarray:
    """Stack slice-rows for batched device transfer."""
    return np.stack(rows) if rows else np.zeros((0, WORDS_PER_SLICE), np.uint32)


def np_group_by(keys: np.ndarray, *arrays: np.ndarray):
    """Yield ``(key, (aligned subarrays...))`` per unique key: ONE stable
    sort plus contiguous slicing — O(n log n) regardless of key
    cardinality, where a per-key boolean mask would re-scan the full
    array per key.  Used by the bulk-import slice grouping."""
    order = np.argsort(keys, kind="stable")
    sk = keys[order]
    sorted_arrays = [a[order] for a in arrays]
    uniq, starts = np.unique(sk, return_index=True)
    bounds = np.append(starts, len(sk))
    for i, k in enumerate(uniq):
        lo, hi = int(bounds[i]), int(bounds[i + 1])
        yield int(k), tuple(a[lo:hi] for a in sorted_arrays)
