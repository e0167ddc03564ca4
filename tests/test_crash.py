"""Hard-crash durability: a REAL server process SIGKILLed mid-write.

The storage engine's promise (reference: fragment.go:379-418 op append,
roaring/roaring.go:622-646 replay) is that everything flushed to the
op-log survives a crash and everything after the last group-commit
boundary is lost cleanly — never a fragment that refuses to load.  This
test boots the actual CLI server in a subprocess, streams SetBit writes
at it over HTTP, SIGKILLs it with writes in flight, then opens the
fragment file the corpse left behind and asserts:

* ``roaring.check`` is clean after open (torn tails repaired),
* the surviving bits are exactly a PREFIX of the write stream (ops are
  appended in order; a crash may truncate, never reorder or corrupt).
"""

import os
import signal
import socket
import subprocess
import sys
import threading
import time

import pytest

from pilosa_tpu.core.fragment import Fragment
from pilosa_tpu.net.client import InternalClient
from pilosa_tpu.ops import roaring

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _boot_server(tmp_path):
    port = _free_port()
    host = f"127.0.0.1:{port}"
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PYTHONPATH=REPO,
    )
    proc = subprocess.Popen(
        [
            sys.executable,
            "-m",
            "pilosa_tpu.cli",
            "server",
            "-d",
            str(tmp_path / "data"),
            "--bind",
            host,
        ],
        env=env,
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
    )
    client = InternalClient(host)
    deadline = time.time() + 90
    while True:
        try:
            client.schema()
            return proc, client
        except Exception:
            if proc.poll() is not None:
                raise RuntimeError("server died during boot")
            if time.time() > deadline:
                proc.kill()
                raise RuntimeError("server never became ready")
            time.sleep(0.2)


@pytest.mark.parametrize("kill_after", [0.3, 1.2])
def test_sigkill_mid_write_recovers_committed_prefix(tmp_path, kill_after):
    proc, client = _boot_server(tmp_path)
    try:
        client.create_index("i")
        client.create_frame("i", "f")

        sent = 0
        stop = threading.Event()
        first_ack = threading.Event()
        errors: list[Exception] = []

        def writer():
            nonlocal sent
            col = 0
            batch = 200
            while not stop.is_set():
                q = "".join(
                    f'SetBit(frame="f", rowID=1, columnID={c})'
                    for c in range(col, col + batch)
                )
                try:
                    client.execute_query("i", q)
                except Exception as e:  # connection dies at the kill
                    errors.append(e)
                    return
                col += batch
                sent = col
                first_ack.set()

        t = threading.Thread(target=writer)
        t.start()
        # The kill timer starts only once a batch is durably acked —
        # otherwise a slow first round-trip makes `sent == 0` flaky.
        assert first_ack.wait(timeout=60), "first batch never acknowledged"
        time.sleep(kill_after)
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=15)
        stop.set()
        t.join(timeout=30)
        assert sent > 0, "no batch was acknowledged before the kill"
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=15)

    fpath = tmp_path / "data" / "i" / "f" / "views" / "standard" / "fragments" / "0"
    assert fpath.exists(), "fragment file missing after crash"

    # Reopen exactly as a restarted server would; open() performs any
    # torn-tail repair.
    f = Fragment(str(fpath), "i", "f", "standard", 0)
    f.open()
    bits = f.row(1).bits()
    f.close()

    # Committed bits are a prefix of the monotone write stream: columns
    # 0..K-1 for some K no larger than what was ever sent (+ one batch
    # that may have been mid-application at the kill).
    assert bits == list(range(len(bits))), "recovered bits are not a prefix"
    assert len(bits) <= sent + 200

    # The on-disk file parses clean after recovery.
    assert roaring.check(fpath.read_bytes()) == []


def test_sigkill_then_full_server_reboot_serves_queries(tmp_path):
    """After a hard kill, a fresh server over the same data dir must
    boot and answer queries from the committed state (reference:
    fragment.go:154-242 open-with-replay)."""
    proc, client = _boot_server(tmp_path)
    try:
        client.create_index("i")
        client.create_frame("i", "f")
        q = "".join(
            f'SetBit(frame="f", rowID=1, columnID={c})' for c in range(3000)
        )
        client.execute_query("i", q)
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=15)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=15)

    proc2, client2 = _boot_server(tmp_path)
    try:
        # Group commit may have lost a buffered suffix, but whatever is
        # there must be a clean prefix and the server must answer.
        count = client2.execute_pql("i", 'Count(Bitmap(frame="f", rowID=1))')
        bm = client2.execute_pql("i", 'Bitmap(frame="f", rowID=1)')
        assert bm.bits() == list(range(count))
    finally:
        proc2.terminate()
        try:
            proc2.wait(timeout=15)
        except subprocess.TimeoutExpired:
            proc2.kill()


def test_sigkill_mid_import_stream_leaves_loadable_fragment(tmp_path):
    """Bulk imports bypass the op-log and snapshot via tmp+rename; a
    SIGKILL anywhere in an import stream must leave a fragment that
    opens clean (pre- or post-rename state, never a torn file)."""
    import numpy as np

    proc, client = _boot_server(tmp_path)
    killed = threading.Event()
    try:
        client.create_index("i")
        client.create_frame("i", "f")

        acked = 0
        errors: list[Exception] = []

        def importer():
            nonlocal acked
            rng = np.random.default_rng(3)
            batch = 0
            while not killed.is_set():
                cols = np.unique(
                    rng.integers(0, 1 << 20, 5000, dtype=np.uint64)
                )
                rows = np.full(len(cols), batch % 7, dtype=np.uint64)
                try:
                    client.import_bits("i", "f", 0, (rows, cols))
                except Exception as e:
                    errors.append(e)
                    return
                acked += len(cols)
                batch += 1

        t = threading.Thread(target=importer)
        t.start()
        deadline = time.time() + 30
        while acked == 0 and time.time() < deadline:
            time.sleep(0.02)
        assert acked > 0, "no import batch acknowledged"
        time.sleep(0.4)  # land the kill mid-stream / mid-snapshot
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=15)
        killed.set()
        t.join(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=15)

    fpath = tmp_path / "data" / "i" / "f" / "views" / "standard" / "fragments" / "0"
    assert fpath.exists()
    f = Fragment(str(fpath), "i", "f", "standard", 0)
    f.open()  # repairs any torn tail; must not raise
    assert f.count() >= 0
    f.close()
    assert roaring.check(fpath.read_bytes()) == []
