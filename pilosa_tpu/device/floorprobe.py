"""One-shot stream-floor probe.

Measures, per local device, the achievable memory-stream bandwidth for
the access pattern the bitmap kernels actually have — a jitted
read-everything reduction over a contiguous uint32 buffer (HBM → VMEM →
VPU, no MXU).  The mean across devices becomes the roofline denominator
(``device.streamFloorGbps``): ``exec.launch.floorPct[site:*]`` is
achieved GB/s over THIS number, the online version of the stream-floor
measurement ``bench.py`` takes.

The probe runs once per process per backend (in-memory cache) and is
additionally cached in the server's artifact dir (``floorprobe.json``)
so restarts skip the measurement.  It is deliberately small —
single-digit MiB per device on CPU, 32 MiB on accelerators
(``PILOSA_FLOORPROBE_BYTES`` overrides) — a floor probe that slows
server open would get turned off.
"""

from __future__ import annotations

import json
import os
import threading
import time

ENV_BYTES = "PILOSA_FLOORPROBE_BYTES"

DEFAULT_PROBE_BYTES = 32 << 20  # accelerator backends
CPU_PROBE_BYTES = 4 << 20  # CPU backend (incl. the virtual test mesh)
WARMUP_ITERS = 1
TIMED_ITERS = 4

CACHE_FILE = "floorprobe.json"

_mu = threading.Lock()
_cache: dict[str, dict] = {}  # backend key -> probe result (per process)


def _backend_key(jax) -> str:
    devs = jax.local_devices()
    kind = getattr(devs[0], "device_kind", "?") if devs else "?"
    return f"{jax.default_backend()}:{kind}:{len(devs)}"


def _probe_bytes(backend: str) -> int:
    env = os.environ.get(ENV_BYTES)
    if env:
        try:
            n = int(env)
            if n > 0:
                return n
        except ValueError:
            pass
    return CPU_PROBE_BYTES if backend == "cpu" else DEFAULT_PROBE_BYTES


def _load_disk(artifact_dir: str | None, key: str) -> dict | None:
    if not artifact_dir:
        return None
    path = os.path.join(artifact_dir, CACHE_FILE)
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
        ent = doc.get(key)
        if isinstance(ent, dict) and "mean_gbps" in ent:
            return ent
    except (OSError, ValueError):
        pass
    return None


def _store_disk(artifact_dir: str | None, key: str, result: dict) -> None:
    if not artifact_dir:
        return
    path = os.path.join(artifact_dir, CACHE_FILE)
    try:
        os.makedirs(artifact_dir, exist_ok=True)
        doc: dict = {}
        try:
            with open(path, encoding="utf-8") as f:
                doc = json.load(f)
            if not isinstance(doc, dict):
                doc = {}
        except (OSError, ValueError):
            pass
        doc[key] = result
        tmp = path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass  # a cache miss next boot, not an error


def _measure(jax, key: str) -> dict:
    import jax.numpy as jnp
    import numpy as np

    backend = jax.default_backend()
    n_bytes = _probe_bytes(backend)
    words = max(1, n_bytes // 4)
    host = np.ones(words, dtype=np.uint32)

    # Read-everything reduction: every word streams HBM->compute once
    # per call.  int32 accumulate keeps the VPU on the integer path the
    # bitmap kernels use (no MXU, no dtype upcast traffic).
    fn = jax.jit(lambda a: jnp.sum(a.astype(jnp.int32)))

    gbps: dict[str, float] = {}
    for dev in jax.local_devices():
        x = jax.device_put(host, dev)
        for _ in range(WARMUP_ITERS):
            fn(x).block_until_ready()  # compile + warm
        t0 = time.monotonic()
        for _ in range(TIMED_ITERS):
            fn(x).block_until_ready()
        dt = time.monotonic() - t0
        g = (words * 4 * TIMED_ITERS / dt / 1e9) if dt > 0 else 0.0
        gbps[str(getattr(dev, "id", len(gbps)))] = round(g, 3)
        del x
    vals = list(gbps.values())
    mean = sum(vals) / len(vals) if vals else 0.0
    return {
        "key": key,
        "probe_bytes": words * 4,
        "iters": TIMED_ITERS,
        "gbps": gbps,
        "mean_gbps": round(mean, 3),
    }


def probe(
    artifact_dir: str | None = None,
    stats=None,
    logger=None,
    force: bool = False,
) -> dict:
    """Measure (or load cached) per-device stream GB/s.

    Returns ``{"key", "probe_bytes", "iters", "gbps": {dev_id: g},
    "mean_gbps"}``.  A device that cannot run the probe's one jitted
    sum cannot run a query either, so a failure here raises (and fails
    ``Server.open``) instead of leaving the floor unset.  Emits the
    ``device.streamFloorGbps`` gauge (aggregate + per-device) when a
    stats client is passed."""
    import jax

    key = _backend_key(jax)
    with _mu:
        cached = None if force else _cache.get(key)
    result = cached
    source = "memory"
    if result is None and not force:
        result = _load_disk(artifact_dir, key)
        source = "disk"
    if result is None:
        result = _measure(jax, key)
        source = "probe"
        _store_disk(artifact_dir, key, result)
    with _mu:
        _cache[key] = result
    if stats is not None:
        stats.gauge("device.streamFloorGbps", result["mean_gbps"])
        for dev_id, g in result["gbps"].items():
            stats.with_tags(f"device:{dev_id}").gauge(
                "device.streamFloorGbps", g
            )
    if logger is not None and source == "probe":
        logger(
            f"stream floor probe: {key} -> {result['mean_gbps']:.1f} GB/s "
            f"mean over {len(result['gbps'])} device(s)"
        )
    return result


def reset_cache() -> None:
    """Tests only: forget in-process probe results."""
    with _mu:
        _cache.clear()
