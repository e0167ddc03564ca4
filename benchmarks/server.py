"""The system under test: one child ``python -m pilosa_tpu.cli server``.

Copied from ``chip_smoke.py`` (PR 21), which stays as it is.  The parent
speaks HTTP only and never initialises a JAX backend; the child owns
the chips.
"""

from __future__ import annotations

import http.client
import json
import os
import re
import signal
import socket
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Every request carries its own deadline: the server's default is 60 s,
# and a request the harness sent is waited for, late or not.
DEADLINE_MS = 600_000

LOG_MUST_NOT_HAVE = (
    "prewarm failed",
    "compilation cache DISABLED",
    "QUARANTINED",
    "watchdog TRIPPED",
    "Traceback (most recent call last)",
)


class HarnessError(RuntimeError):
    """The run could not be made; no result line is printed."""


class Server:
    def __init__(self, data_dir: str, log_path: str, extra_env: dict,
                 argv: list[str] | None = None):
        self.data_dir = data_dir
        self.log_path = log_path
        self.extra_env = extra_env
        # Tests put a wrapper here that breaks the timed path underneath.
        self.argv = argv or [sys.executable, "-m", "pilosa_tpu.cli", "server"]
        self.proc: subprocess.Popen | None = None
        self.port = 0

    def start(self) -> None:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        env = dict(os.environ)
        env["PILOSA_METRICS_SERVICE"] = "expvar"
        env["PYTHONUNBUFFERED"] = "1"
        env.update(self.extra_env)
        with open(self.log_path, "ab") as logf:
            self.proc = subprocess.Popen(
                self.argv + [
                    "--data-dir", self.data_dir,
                    "--bind", f"127.0.0.1:{self.port}",
                ],
                cwd=ROOT, env=env, stdin=subprocess.DEVNULL,
                stdout=logf, stderr=subprocess.STDOUT, start_new_session=True,
            )

    def log_text(self) -> str:
        with open(self.log_path, "rb") as f:
            return f.read().decode("utf-8", "replace")

    def wait_listening(self, timeout: float) -> None:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise HarnessError(
                    f"server exited with {self.proc.returncode} during boot:\n"
                    + self.log_text()[-4000:]
                )
            if "listening on http://" in self.log_text():
                return
            time.sleep(0.1)
        raise HarnessError(f"server not listening after {timeout:.0f} s")

    def device(self) -> dict:
        m = re.search(
            r"devices: platform=(\S+) kind='([^']*)' count=(\d+)", self.log_text()
        )
        if m is None:
            raise HarnessError("server logged no 'devices:' line")
        return {"platform": m.group(1), "kind": m.group(2), "count": int(m.group(3))}

    def stop(self, timeout: float = 120.0) -> int:
        """SIGTERM, wait for the exit; SIGKILL the group past ``timeout``."""
        if self.proc is None or self.proc.poll() is not None:
            return self.proc.returncode if self.proc else 0
        self.proc.send_signal(signal.SIGTERM)
        try:
            return self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.kill()
            return -9

    def kill(self) -> None:
        if self.proc is not None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
            self.proc.wait()

    # -- HTTP ---------------------------------------------------------------

    def connect(self) -> http.client.HTTPConnection:
        return http.client.HTTPConnection(
            "127.0.0.1", self.port, timeout=DEADLINE_MS / 1000 + 30
        )

    def request(self, method: str, path: str, body: bytes | None = None):
        conn = self.connect()
        try:
            conn.request(method, path, body=body,
                         headers={"X-Deadline-Ms": str(DEADLINE_MS)})
            resp = conn.getresponse()
            return resp.status, resp.read()
        finally:
            conn.close()

    def get_json(self, path: str):
        status, data = self.request("GET", path)
        if status != 200:
            raise HarnessError(f"GET {path} -> {status}: {data[:300]!r}")
        return json.loads(data)

    def metrics(self) -> dict[str, float]:
        """``/metrics`` as {series: value}."""
        status, data = self.request("GET", "/metrics")
        if status != 200:
            raise HarnessError(f"GET /metrics -> {status}")
        out = {}
        for line in data.decode().splitlines():
            if line and not line.startswith("#"):
                series, _, value = line.rpartition(" ")
                try:
                    out[series] = float(value)
                except ValueError:
                    pass
        return out
