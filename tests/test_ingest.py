"""Durable ingest subsystem (pilosa_tpu/ingest): WAL framing, group
commit, crash recovery, and the device delta-scatter path.

Crash simulation: while a fragment is open its op-log tail lives in
``_op_buf`` (flushed at 64 KiB or close) — copying the data file + the
``.wal`` segment of a LIVE fragment is therefore exactly the disk image
a ``kill -9`` leaves behind.  Recovery over that image must restore
every durably-logged bit; the ``tools/ingest_smoke.py`` CI pass does the
same with a real SIGKILL'd process.
"""

from __future__ import annotations

import os
import shutil
import threading
import time

import numpy as np
import pytest

from pilosa_tpu.core.fragment import Fragment
from pilosa_tpu.ingest import scatter as ingest_scatter
from pilosa_tpu.ingest import wal as ingest_wal
from pilosa_tpu.ops import bitplane as bp
from pilosa_tpu.ops import roaring


@pytest.fixture
def managed(tmp_path):
    """An IngestManager registered over tmp_path plus a fragment opened
    under it (so Fragment.open attaches a WAL writer)."""
    # The manager owns tmp_path/"data" only, so crash images copied to
    # sibling dirs attach to THEIR OWN manager, not this one.
    mgr = ingest_wal.IngestManager(str(tmp_path / "data"), group_commit_ms=1.0)
    ingest_wal.register_manager(mgr)
    frag = Fragment(str(tmp_path / "data" / "0"), "i", "f", "standard", 0)
    frag.open()
    try:
        yield mgr, frag
    finally:
        frag.close()
        ingest_wal.unregister_manager(mgr)
        mgr.close()


def crash_image(frag, dst_dir):
    """Copy a LIVE fragment's on-disk state (data file + WAL segment):
    what a kill -9 leaves behind — buffered ops and all host state gone."""
    os.makedirs(dst_dir, exist_ok=True)
    dst = os.path.join(dst_dir, os.path.basename(frag.path))
    shutil.copy(frag.path, dst)
    wp = ingest_wal.wal_path(frag.path)
    if os.path.exists(wp):
        shutil.copy(wp, ingest_wal.wal_path(dst))
    return dst


class TestWalFraming:
    def _write(self, path, base, snap_size, frames):
        with open(path, "wb") as fh:
            fh.write(ingest_wal.encode_header(base, snap_size))
            v = base
            for ops in frames:
                payload = b"".join(
                    roaring.encode_op(typ, pos) for typ, pos in ops
                )
                v += len(ops)
                fh.write(ingest_wal.encode_frame(payload, len(ops), v))

    def test_roundtrip(self, tmp_path):
        p = str(tmp_path / "seg.wal")
        self._write(p, 7, 123, [
            [(roaring.OP_ADD, 5), (roaring.OP_ADD, 9)],
            [(roaring.OP_REMOVE, 5)],
        ])
        seg = ingest_wal.load_segment(p)
        assert seg is not None and not seg.torn
        assert (seg.base_op_version, seg.snap_size) == (7, 123)
        assert seg.n_ops == 3
        assert seg.end_op_version == 10
        assert [f[0] for f in seg.frames] == [9, 10]
        assert seg.good_bytes == os.path.getsize(p)

    def test_missing_and_corrupt_header(self, tmp_path):
        assert ingest_wal.load_segment(str(tmp_path / "nope.wal")) is None
        p = str(tmp_path / "bad.wal")
        with open(p, "wb") as fh:
            fh.write(b"JUNK" + b"\0" * 20)
        assert ingest_wal.load_segment(p) is None

    def test_torn_tail_stops_at_first_bad_frame(self, tmp_path):
        p = str(tmp_path / "seg.wal")
        self._write(p, 0, 0, [[(roaring.OP_ADD, 1)], [(roaring.OP_ADD, 2)]])
        good = os.path.getsize(p)
        with open(p, "ab") as fh:
            # Half a frame: header promising more bytes than exist.
            fh.write(ingest_wal._FRAME.pack(roaring.OP_SIZE, 1, 3))
            fh.write(b"\x01\x02")
        seg = ingest_wal.load_segment(p)
        assert seg.torn and seg.n_ops == 2
        assert seg.good_bytes == good
        assert seg.problem == "torn frame"

    def test_checksum_reject(self, tmp_path):
        p = str(tmp_path / "seg.wal")
        self._write(p, 0, 0, [[(roaring.OP_ADD, 1)], [(roaring.OP_ADD, 2)]])
        data = bytearray(open(p, "rb").read())
        # Flip one payload bit inside the SECOND frame.
        second = (ingest_wal.HEADER_SIZE + ingest_wal.FRAME_HEADER_SIZE
                  + roaring.OP_SIZE + ingest_wal.DIGEST_SIZE)
        data[second + ingest_wal.FRAME_HEADER_SIZE] ^= 0x40
        open(p, "wb").write(bytes(data))
        seg = ingest_wal.load_segment(p)
        assert seg.torn and seg.n_ops == 1
        assert seg.problem == "frame checksum mismatch"

    def test_version_gap_rejects(self, tmp_path):
        p = str(tmp_path / "seg.wal")
        with open(p, "wb") as fh:
            fh.write(ingest_wal.encode_header(0, 0))
            payload = roaring.encode_op(roaring.OP_ADD, 1)
            # end_op_version 5 after one op from base 0: a gap.
            fh.write(ingest_wal.encode_frame(payload, 1, 5))
        seg = ingest_wal.load_segment(p)
        assert seg.torn and seg.n_ops == 0
        assert seg.problem == "bad frame header"


class TestGroupCommit:
    def test_ack_is_durable(self, managed):
        mgr, frag = managed
        frag.set_bit(3, 17)
        mgr.wait_durable()
        seg = ingest_wal.load_segment(ingest_wal.wal_path(frag.path))
        assert seg.n_ops == 1 and not seg.torn

    def test_32_writers_batch_into_few_fsyncs(self, managed):
        mgr, frag = managed
        threads, writes = 32, 12

        def storm(t):
            for k in range(writes):
                frag.set_bit(t, k)
                mgr.wait_durable()

        ts = [threading.Thread(target=storm, args=(t,))
              for t in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        snap = mgr.snapshot()
        total = threads * writes
        assert snap["totalAppends"] == total
        # The whole point of group commit: concurrent durable writers
        # share fsyncs.  Strictly fewer than one per write, with real
        # batching margin.
        assert 1 <= snap["totalFsyncs"] <= total // 4
        seg = ingest_wal.load_segment(ingest_wal.wal_path(frag.path))
        assert seg.n_ops == total and not seg.torn

    def test_snapshot_truncates_segment(self, managed):
        mgr, frag = managed
        for c in range(8):
            frag.set_bit(1, c)
        mgr.wait_durable()
        frag.snapshot()
        seg = ingest_wal.load_segment(ingest_wal.wal_path(frag.path))
        assert seg.frames == []
        assert seg.base_op_version == 8
        # New writes land in the fresh segment at the new base.
        frag.set_bit(1, 100)
        mgr.wait_durable()
        seg = ingest_wal.load_segment(ingest_wal.wal_path(frag.path))
        assert seg.n_ops == 1 and seg.end_op_version == 9

    def test_write_after_manager_close_degrades(self, managed):
        mgr, frag = managed
        frag.set_bit(0, 1)
        mgr.wait_durable()
        mgr.close()
        # Ack path degrades to pre-WAL durability instead of raising.
        assert frag.set_bit(0, 2)
        assert frag.contains(0, 2)


class TestRecovery:
    def test_replay_restores_acked_bits(self, managed, tmp_path):
        mgr, frag = managed
        bits = [(3, 17), (3, 400), (9, 64), (0, 0)]
        for r, c in bits:
            frag.set_bit(r, c)
        frag.clear_bit(3, 400)
        mgr.wait_durable()
        img = crash_image(frag, str(tmp_path / "crash"))

        mgr2 = ingest_wal.IngestManager(str(tmp_path / "crash"))
        ingest_wal.register_manager(mgr2)
        try:
            f2 = Fragment(img, "i", "f", "standard", 0)
            f2.open()
            try:
                assert f2.contains(3, 17)
                assert f2.contains(9, 64)
                assert f2.contains(0, 0)
                assert not f2.contains(3, 400)
                rep = mgr2._last_replay
                assert rep["walOps"] == 5 and rep["skipped"] == 0
                assert rep["replayed"] == 5
            finally:
                f2.close()
        finally:
            ingest_wal.unregister_manager(mgr2)
            mgr2.close()

    def test_replay_skips_ops_before_snapshot(self, managed, tmp_path):
        mgr, frag = managed
        for c in range(4):
            frag.set_bit(1, c)
        mgr.wait_durable()
        frag.snapshot()  # truncates: base_op_version = 4
        for c in range(4, 7):
            frag.set_bit(1, c)
        mgr.wait_durable()
        img = crash_image(frag, str(tmp_path / "crash"))

        mgr2 = ingest_wal.IngestManager(str(tmp_path / "crash"))
        ingest_wal.register_manager(mgr2)
        try:
            f2 = Fragment(img, "i", "f", "standard", 0)
            f2.open()
            try:
                assert [c for c in range(7) if f2.contains(1, c)] == list(
                    range(7)
                )
                rep = mgr2._last_replay
                # Only the 3 post-snapshot ops were in the segment.
                assert rep["walOps"] == 3 and rep["replayed"] == 3
            finally:
                f2.close()
        finally:
            ingest_wal.unregister_manager(mgr2)
            mgr2.close()

    def test_clean_reopen_replays_nothing(self, tmp_path):
        mgr = ingest_wal.IngestManager(str(tmp_path))
        ingest_wal.register_manager(mgr)
        try:
            path = str(tmp_path / "i" / "0")
            frag = Fragment(path, "i", "f", "standard", 0)
            frag.open()
            for c in range(5):
                frag.set_bit(2, c)
            frag.close()  # flushes the op-log tail + final WAL commit
            f2 = Fragment(path, "i", "f", "standard", 0)
            f2.open()
            try:
                assert all(f2.contains(2, c) for c in range(5))
                rep = mgr._last_replay
                # Every WAL op was already in the data file's op-log.
                assert rep is not None and rep["replayed"] == 0
                assert rep["skipped"] == rep["walOps"]
            finally:
                f2.close()
        finally:
            ingest_wal.unregister_manager(mgr)
            mgr.close()

    def test_torn_tail_replays_verified_prefix(self, managed, tmp_path):
        mgr, frag = managed
        for c in range(6):
            frag.set_bit(5, c)
        mgr.wait_durable()
        img = crash_image(frag, str(tmp_path / "crash"))
        # Tear the copied segment mid-frame (crash during the append).
        wp = ingest_wal.wal_path(img)
        sz = os.path.getsize(wp)
        with open(wp, "r+b") as fh:
            fh.truncate(sz - 10)

        mgr2 = ingest_wal.IngestManager(str(tmp_path / "crash"))
        ingest_wal.register_manager(mgr2)
        try:
            f2 = Fragment(img, "i", "f", "standard", 0)
            f2.open()
            try:
                rep = mgr2._last_replay
                assert rep["torn"] is True
                # The verified prefix replays; the torn frame's ops are
                # exactly the never-acked set.
                present = [c for c in range(6) if f2.contains(5, c)]
                assert len(present) == rep["replayed"]
                assert present == list(range(rep["replayed"]))
            finally:
                f2.close()
        finally:
            ingest_wal.unregister_manager(mgr2)
            mgr2.close()

    def test_stale_segment_discarded(self, managed, tmp_path):
        mgr, frag = managed
        frag.set_bit(1, 1)
        mgr.wait_durable()
        img = crash_image(frag, str(tmp_path / "crash"))
        # Run a snapshot on the crash image while no WAL manager owns it
        # (as if [ingest] wal was toggled off for a maintenance window):
        # the data file's snapshot region is rewritten, so the copied
        # segment's snap_size no longer matches and it must be
        # discarded, not replayed against the wrong base.  Bit (1,1)
        # lived only in the forfeited WAL, so it is gone — the
        # documented cost of snapshotting while detached.
        f_tmp = Fragment(img, "i", "f", "standard", 0)
        f_tmp.open()
        f_tmp.set_bit(8, 8)
        f_tmp.snapshot()
        f_tmp.close()

        mgr2 = ingest_wal.IngestManager(str(tmp_path / "crash"))
        ingest_wal.register_manager(mgr2)
        try:
            f2 = Fragment(img, "i", "f", "standard", 0)
            f2.open()
            try:
                assert mgr2._last_replay is None  # discarded, no replay
                assert f2.contains(8, 8) and not f2.contains(1, 1)
            finally:
                f2.close()
        finally:
            ingest_wal.unregister_manager(mgr2)
            mgr2.close()

    def test_diverged_oplog_discards_segment(self, managed, tmp_path):
        mgr, frag = managed
        frag.set_bit(1, 1)
        mgr.wait_durable()
        img = crash_image(frag, str(tmp_path / "crash"))
        # Write to the crash image while no WAL manager owns it: its
        # data op-log gains ops the WAL never saw, so the segment's op
        # sequence and the data file's diverge.  snap_size still
        # matches (op-log appends don't move the snapshot region), so
        # this exercises the byte-prefix check specifically.
        f_tmp = Fragment(img, "i", "f", "standard", 0)
        f_tmp.open()
        f_tmp.set_bit(8, 8)
        f_tmp.close()  # flushes (8,8) into the data op-log, no WAL

        mgr2 = ingest_wal.IngestManager(str(tmp_path / "crash"))
        ingest_wal.register_manager(mgr2)
        try:
            f2 = Fragment(img, "i", "f", "standard", 0)
            f2.open()
            try:
                assert mgr2._last_replay is None  # discarded, no replay
                assert f2.contains(8, 8) and not f2.contains(1, 1)
            finally:
                f2.close()
        finally:
            ingest_wal.unregister_manager(mgr2)
            mgr2.close()


class TestSnapshotDurability:
    def test_snapshot_fsyncs_file_and_directory(self, tmp_path, monkeypatch):
        """Regression (this PR's bugfix): the snapshot's atomic rename
        is durable only after the *directory* entry is fsynced — a crash
        after rename but before dir sync can resurrect the pre-snapshot
        file."""
        frag = Fragment(str(tmp_path / "0"), "i", "f", "standard", 0)
        frag.open()
        try:
            frag.set_bit(0, 1)
            calls = []
            real = ingest_wal._fsync_dir
            monkeypatch.setattr(
                ingest_wal, "_fsync_dir",
                lambda p: calls.append(p) or real(p),
            )
            fsyncs = []
            real_fsync = os.fsync
            monkeypatch.setattr(
                os, "fsync", lambda fd: fsyncs.append(fd) or real_fsync(fd)
            )
            frag.snapshot()
            assert frag.path in calls, "snapshot skipped the dir fsync"
            assert fsyncs, "snapshot skipped the data-file fsync"
        finally:
            frag.close()


class TestDeltaScatter:
    def _storm(self, frag, rng, rows=4, n=300):
        cols = rng.integers(0, 4096, size=n)
        row_ids = rng.integers(0, rows, size=n)
        ops = rng.integers(0, 2, size=n)
        for r, c, op in zip(row_ids, cols, ops):
            if op:
                frag.set_bit(int(r), int(c))
            else:
                frag.clear_bit(int(r), int(c))

    def test_randomized_storm_byte_identity_vs_invalidate(
        self, tmp_path, rng, monkeypatch
    ):
        """The scatter-applied mirror must be byte-identical to the
        invalidate + full re-upload path across a randomized set/clear
        storm (device reads interleaved so deltas actually fold)."""
        fa = Fragment(str(tmp_path / "a"), "i", "f", "standard", 0)
        fb = Fragment(str(tmp_path / "b"), "i", "f", "standard", 0)
        fa.open()
        fb.open()
        try:
            for f in (fa, fb):
                f.set_bit(0, 9)
                f.device_plane()  # engage the mirror
            seed = int(rng.integers(0, 1 << 31))
            for chunk in range(6):
                r1 = np.random.default_rng(seed + chunk)
                r2 = np.random.default_rng(seed + chunk)
                monkeypatch.setattr(ingest_scatter, "ENABLED", True)
                self._storm(fa, r1)
                monkeypatch.setattr(ingest_scatter, "ENABLED", False)
                self._storm(fb, r2)
                monkeypatch.setattr(ingest_scatter, "ENABLED", True)
                for row in range(4):
                    a = np.asarray(fa.device_row(row))
                    monkeypatch.setattr(ingest_scatter, "ENABLED", False)
                    b = np.asarray(fb.device_row(row))
                    monkeypatch.setattr(ingest_scatter, "ENABLED", True)
                    np.testing.assert_array_equal(a, b)
            assert fa._device is not None, "scatter path lost the mirror"
            assert fb._device is not None
        finally:
            fa.close()
            fb.close()

    def test_import_bulk_paths_byte_identity(self, tmp_path, rng, monkeypatch):
        fa = Fragment(str(tmp_path / "a"), "i", "f", "standard", 0)
        fb = Fragment(str(tmp_path / "b"), "i", "f", "standard", 0)
        fa.open()
        fb.open()
        try:
            for f in (fa, fb):
                f.set_bit(0, 1)
                f.device_plane()
            rows = rng.integers(0, 3, size=64).tolist()
            cols = rng.integers(0, 2048, size=64).tolist()
            monkeypatch.setattr(ingest_scatter, "ENABLED", True)
            fa.import_bulk(rows, cols)
            monkeypatch.setattr(ingest_scatter, "ENABLED", False)
            fb.import_bulk(rows, cols)
            for row in range(3):
                monkeypatch.setattr(ingest_scatter, "ENABLED", True)
                a = np.asarray(fa.device_row(row))
                monkeypatch.setattr(ingest_scatter, "ENABLED", False)
                b = np.asarray(fb.device_row(row))
                np.testing.assert_array_equal(a, b)
        finally:
            fa.close()
            fb.close()

    def test_untouched_row_read_skips_sync(self, tmp_path):
        """A read of a row the queued deltas DON'T touch serves the
        resident mirror as-is: no scatter launch, no re-stage — the
        ingest-storm-on-other-rows read path."""
        from pilosa_tpu.device import pool
        from pilosa_tpu.exec import plan  # noqa: F401 (warm import)

        frag = Fragment(str(tmp_path / "0"), "i", "f", "standard", 0)
        frag.open()
        try:
            for c in range(0, 512, 5):
                frag.set_bit(1, c)
            frag.set_bit(2, 7)
            before_a = np.asarray(frag.device_row(1))  # stage + sync
            launches0 = ingest_scatter.counters()["launches"]
            restage0 = pool().restage_bytes()
            for c in range(32):
                frag.set_bit(2, 100 + c)  # storm on row 2 only
            a = np.asarray(frag.device_row(1))  # untouched row
            assert ingest_scatter.counters()["launches"] == launches0
            assert pool().restage_bytes() == restage0
            np.testing.assert_array_equal(a, before_a)
            # Reading the STORMED row must sync (one launch) and see
            # every bit.
            b = np.asarray(frag.device_row(2))
            assert ingest_scatter.counters()["launches"] == launches0 + 1
            got = {
                int(w) * 32 + s
                for w, word in enumerate(b)
                for s in range(32)
                if int(word) >> s & 1
            }
            assert got == {7} | {100 + c for c in range(32)}
        finally:
            frag.close()

    def test_committer_applies_scatter_in_background(self, managed):
        """The group-commit tick folds queued deltas into the mirror
        off the read path: after a durable write, the pending queue
        drains without any device read."""
        mgr, frag = managed
        frag.set_bit(0, 3)
        frag.device_plane()  # stage the mirror
        frag.set_bit(0, 99)
        mgr.wait_durable()
        deadline = time.time() + 5.0
        while time.time() < deadline:
            with frag._mu:
                if (
                    not frag._device_pending
                    and frag._device_version == frag._version
                    and frag._device is not None
                ):
                    break
            time.sleep(0.01)
        else:
            raise AssertionError(
                "committer never applied pending scatter: "
                f"pending={len(frag._device_pending)}"
            )
        row = np.asarray(frag.device_row(0))
        assert int(row[3 // 32]) >> (3 % 32) & 1
        assert int(row[99 // 32]) >> (99 % 32) & 1

    def test_fold_last_wins(self):
        # (slot, word, mask, op): set bit 3, clear bit 3, set bit 5 —
        # the fold must cancel per bit with later ops winning.
        pending = [(0, 1, 1 << 3, 1), (0, 1, 1 << 3, 0), (0, 1, 1 << 5, 1)]
        slots, words, or_m, andnot_m = ingest_scatter.fold(pending)
        assert slots.tolist() == [0] and words.tolist() == [1]
        assert or_m.tolist() == [1 << 5]
        assert andnot_m.tolist() == [1 << 3]

    def test_pow2_bucketing_bounds_program_cache(self, tmp_path):
        """Entries stay inside the bound whatever ran before in this
        process: update counts bucket to powers of two, and a plane on
        another home device is another executable, which the bound
        counts (slices 0 and 1 live on two devices here)."""
        from pilosa_tpu.exec import plan

        before = plan.program_cache_stats().get("plan.scatter", 0)
        assert bp.home_device(0) != bp.home_device(1)
        for slice_i in (0, 1):
            base = slice_i * bp.SLICE_WIDTH
            frag = Fragment(
                str(tmp_path / str(slice_i)), "i", "f", "standard", slice_i
            )
            frag.open()
            try:
                frag.set_bit(0, base)
                frag.device_plane()
                for n in (1, 2, 3, 5, 9, 17):
                    for c in range(n):
                        frag.set_bit(1, base + 64 * c)
                    frag.device_row(1)
            finally:
                frag.close()
        stats = plan.program_cache_stats()
        bounds = plan.program_cache_bounds()
        assert stats.get("plan.scatter", 0) >= 1
        assert stats["plan.scatter"] <= bounds["plan.scatter"]
        # Six update counts under the 32-update floor are ONE bucket:
        # at most one new executable a device.
        assert stats["plan.scatter"] - before <= 2

    def test_concurrent_reader_sees_atomic_planes(self, tmp_path):
        """A reader racing a set-only storm must only ever observe a
        subset of the final bits (atomic plane versions — never a
        half-applied scatter or a torn mirror)."""
        frag = Fragment(str(tmp_path / "0"), "i", "f", "standard", 0)
        frag.open()
        try:
            frag.set_bit(0, 0)
            frag.device_plane()
            final = {0} | {c for c in range(1, 512, 3)}
            stop = threading.Event()
            bad: list = []

            def reader():
                while not stop.is_set():
                    row = np.asarray(frag.device_row(0))
                    got = set(bp.np_row_to_columns(row).tolist())
                    if not got <= final:
                        bad.append(got - final)
                        return

            t = threading.Thread(target=reader)
            t.start()
            for c in range(1, 512, 3):
                frag.set_bit(0, c)
            stop.set()
            t.join(timeout=30)
            assert not bad, f"reader saw bits outside the final set: {bad[:3]}"
            got = set(
                bp.np_row_to_columns(np.asarray(frag.device_row(0))).tolist()
            )
            assert got == final
        finally:
            frag.close()


class TestConfig:
    def test_ingest_config_roundtrip_and_env(self):
        from pilosa_tpu import config as config_mod
        from pilosa_tpu.config import Config

        cfg = Config()
        assert cfg.ingest.wal is True
        assert cfg.ingest.group_commit_ms == 2.0
        doc = cfg.to_toml()
        assert "[ingest]" in doc
        back = config_mod.from_toml(doc)
        assert back.ingest.group_commit_max == cfg.ingest.group_commit_max

        cfg = config_mod.apply_env(Config(), {
            "PILOSA_INGEST_WAL": "false",
            "PILOSA_INGEST_GROUP_COMMIT_MS": "7.5",
            "PILOSA_INGEST_SCATTER": "0",
            "PILOSA_INGEST_WAL_SEGMENT_BYTES": "65536",
        })
        assert cfg.ingest.wal is False
        assert cfg.ingest.group_commit_ms == 7.5
        assert cfg.ingest.scatter is False
        assert cfg.ingest.wal_segment_bytes == 65536

    def test_validate_rejects_bad_values(self):
        from pilosa_tpu.config import Config, ConfigError

        cfg = Config()
        cfg.ingest.group_commit_ms = -1.0
        with pytest.raises(ConfigError):
            cfg.validate()
        cfg = Config()
        cfg.ingest.group_commit_max = 0
        with pytest.raises(ConfigError):
            cfg.validate()
