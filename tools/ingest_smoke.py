"""CI smoke for the durable ingest subsystem (``make ingest-smoke``):
a child process takes a sustained multi-threaded quorum write storm —
every ack reported to the parent only AFTER the executor's durability
wait (i.e. after the WAL group commit fsynced the record) — and is
``kill -9``'d mid-storm.  The parent then reopens the same data dir and
asserts

* ZERO lost acked bits: every column the child acked before the kill is
  present in the restarted holder's fragments (host oracle via
  ``Fragment.contains``);
* recovery actually ran: the restarted manager reports >= 1 WAL replay
  with > 0 replayed ops — proving the bits came back from the log, not
  from a data-file flush (the storm stays far below the 64 KiB op-log
  flush threshold, so without the WAL every storm bit would be lost);
* the child was genuinely killed mid-storm (it never exited on its own).

Deterministic CPU pass; BLOCKING in CI (.github/workflows/check.yml)
under ``PILOSA_LOCK_CHECK=1`` like subscribe-smoke.
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import tempfile
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

WRITERS = 4
# Kill once this many acks crossed the pipe; the per-thread write cap is
# far larger so the storm can never finish before the kill.
MIN_ACKS = int(os.environ.get("INGEST_SMOKE_MIN_ACKS", "200"))
WRITES_PER_THREAD = 200_000


def child(data_dir: str) -> int:
    """Storm process: single node, WAL on, acks printed only after the
    write returned (durability wait included).  Runs until killed."""
    from pilosa_tpu.net.handler import Request
    from pilosa_tpu.net.server import Server

    srv = Server(data_dir=data_dir, host="127.0.0.1:0",
                 anti_entropy_interval=3600, polling_interval=3600)
    srv.open()
    srv.holder.create_index_if_not_exists("i")
    srv.holder.index("i").create_frame_if_not_exists("f")
    out_mu = threading.Lock()

    def storm(row: int) -> None:
        for k in range(WRITES_PER_THREAD):
            col = k * WRITERS + row
            q = f'SetBit(frame="f", rowID={row}, columnID={col})'
            r = srv.handler.dispatch(
                Request("POST", "/index/i/query", body=q.encode())
            )
            if r.status != 200:
                with out_mu:
                    print(f"ERR {r.status} {r.body!r}", flush=True)
                return
            # The dispatch above returned only after the executor's
            # durability wait: this record is on disk.  The print is the
            # ack the parent's oracle records — kernel pipe buffering
            # preserves it across our own SIGKILL.
            with out_mu:
                print(f"ACK {row} {col}", flush=True)

    threads = [
        threading.Thread(target=storm, args=(t,), daemon=True)
        for t in range(WRITERS)
    ]
    print("READY", flush=True)
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # Reaching here means the parent failed to kill us mid-storm.
    print("DONE", flush=True)
    srv.close()
    return 0


def main() -> int:
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        return child(sys.argv[2])

    tmp = tempfile.mkdtemp(prefix="ingest-smoke-")
    data_dir = os.path.join(tmp, "node")
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child", data_dir],
        stdout=subprocess.PIPE,
        text=True,
    )
    acked: set[tuple[int, int]] = set()
    errors: list[str] = []
    done = threading.Event()

    def reader() -> None:
        for line in proc.stdout:
            parts = line.split()
            if parts and parts[0] == "ACK":
                acked.add((int(parts[1]), int(parts[2])))
            elif parts and parts[0] == "ERR":
                errors.append(line.strip())
            elif parts and parts[0] == "DONE":
                errors.append("storm finished before the kill")
        done.set()

    t = threading.Thread(target=reader, daemon=True)
    t.start()

    deadline = time.time() + 180
    while time.time() < deadline:
        if errors or done.is_set():
            break
        if len(acked) >= MIN_ACKS:
            break
        time.sleep(0.02)

    killed_mid_storm = proc.poll() is None and not done.is_set()
    if killed_mid_storm:
        os.kill(proc.pid, signal.SIGKILL)
        print(f"[ingest-smoke] kill -9 after {len(acked)} acks",
              file=sys.stderr)
    proc.wait(timeout=30)
    # Drain acks that reached the pipe before the kill.
    done.wait(timeout=30)

    if errors:
        print(f"FAIL: {errors[:3]}", file=sys.stderr)
        return 1
    if not killed_mid_storm:
        print("FAIL: child exited before the mid-storm kill", file=sys.stderr)
        return 1
    if len(acked) < MIN_ACKS:
        print(f"FAIL: only {len(acked)} acks before deadline", file=sys.stderr)
        return 1

    # RESTART: reopen the same data dir; recovery replays the WAL tail.
    from pilosa_tpu.net.server import Server

    srv = Server(data_dir=data_dir, host="127.0.0.1:0",
                 anti_entropy_interval=3600, polling_interval=3600)
    srv.open()
    try:
        snap = srv.ingest.snapshot()
        view = srv.holder.index("i").frame("f").view("standard")
        from pilosa_tpu.ops.bitplane import SLICE_WIDTH

        lost = []
        for row, col in sorted(acked):
            frag = view.fragment(col // SLICE_WIDTH)
            if frag is None or not frag.contains(row, col):
                lost.append((row, col))
    finally:
        srv.close()

    if lost:
        print(f"FAIL: {len(lost)} acked bits lost after kill -9: "
              f"{lost[:10]}", file=sys.stderr)
        return 1
    if snap["replays"] < 1 or snap["replayedOps"] < 1:
        print(f"FAIL: restart did not replay the WAL "
              f"(replays={snap['replays']} ops={snap['replayedOps']}) — "
              "the acked bits survived by some other path", file=sys.stderr)
        return 1
    print(
        f"OK: kill -9 mid-storm lost zero of {len(acked)} acked bits; "
        f"restart replayed {snap['replayedOps']} WAL ops across "
        f"{snap['replays']} fragments"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
