"""Test fixtures: force JAX onto a virtual 8-device CPU mesh.

The distributed logic must be testable without a TPU pod (SURVEY.md §4
implication), so every test runs on the CPU backend with 8 virtual
devices; the chip is reached only through ``chip_smoke.py`` (see
PERF.md).
"""

import os

# Tests never touch a chip: force the CPU backend, with 8 virtual
# devices for the mesh paths.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# No persistent compile cache for the servers tests boot (their own
# processes and the children they spawn): on the CPU backend every hit
# makes XLA log two long "Loading XLA:CPU AOT result" lines, enough of
# which fill a child's undrained pipe.  Tests of the cache itself name
# their directories.
os.environ.setdefault("PILOSA_TPU_COMPILATION_CACHE_DIR", "off")

import jax  # noqa: E402

# An interpreter that imported jax before this file ran has already
# read JAX_PLATFORMS, so update the live config too, before any backend
# initializes.
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_sessionfinish(session, exitstatus):
    """PILOSA_LOCK_CHECK=1: after the suite, assert every lock
    acquisition order observed at runtime is consistent with the static
    lock graph (pilosa_tpu/analyze) — the analyzer is proven against
    reality on every instrumented run, not just committed."""
    if not os.environ.get("PILOSA_LOCK_CHECK"):
        return
    from pilosa_tpu.analyze import runtime as lock_check

    problems = lock_check.verify()
    rep = session.config.pluginmanager.get_plugin("terminalreporter")
    lines = [lock_check.report().splitlines()[0]]
    if problems:
        lines.append("lock-check: STATIC/RUNTIME DISAGREEMENT")
        lines.extend("  " + p for p in problems)
        session.exitstatus = 1
    else:
        lines.append("lock-check: runtime acquisition order consistent "
                     "with the static lock graph")
    for ln in lines:
        if rep is not None:
            rep.write_line(ln)
        else:
            print(ln)


@pytest.fixture
def rng():
    return np.random.default_rng(42)


def positions_to_words(positions, n_words=1024):
    """Pack bit positions into uint64 words — shared by the roaring,
    native-parity, and property test suites."""
    w = np.zeros(n_words, dtype=np.uint64)
    for p in positions:
        w[p // 64] |= np.uint64(1) << np.uint64(p % 64)
    return w


def free_udp_port() -> int:
    """Reserve-and-release a local UDP port — shared by the gossip unit
    tests and the multi-node cluster tests."""
    import socket

    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.fixture
def one_chip():
    """Every slice's home is one device, as on a one-chip machine (the
    tests' CPU has eight, and fragments are grouped by their home)."""
    from pilosa_tpu.ops import bitplane as bp

    bp.configure_mesh_devices(1)
    yield
    bp.configure_mesh_devices(0)
