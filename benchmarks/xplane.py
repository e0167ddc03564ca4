"""From a profiler trace to device intervals, and from intervals to
busy time, idle gaps and the top operations.

``python benchmarks/xplane.py <trace.xplane.pb>`` is run as a child with
``JAX_PLATFORMS=cpu`` once the server has stopped: reading the file
needs ``jax`` and the harness itself never touches it.  It prints one
JSON object: ``start`` / ``stop`` (the profile session's wall-clock
bounds, seconds) and, per device plane, the operations of its op line as
``[name, start_s, duration_s]`` on that same clock.

The arithmetic below is plain Python on such lists, so that the tests
pin it on a hand-made one.
"""

from __future__ import annotations

import json
import sys

# The line of a device plane that holds one event per executed HLO op.
# Module and step lines cover the same time again and are not added.
OP_LINE = "XLA Ops"


def read_trace(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    start_ns = stop_ns = None
    devices: dict[str, list] = {}
    for plane in data.planes:
        if plane.name == "Task Environment":
            stats = dict(plane.stats)
            start_ns = stats.get("profile_start_time")
            stop_ns = stats.get("profile_stop_time")
        if not plane.name.startswith("/device:"):
            continue
        for ln in plane.lines:
            if ln.name != OP_LINE:
                continue
            devices[plane.name] = [
                [ev.name, ev.start_ns, ev.duration_ns] for ev in ln.events
            ]
    if start_ns is None or stop_ns is None:
        raise SystemExit("the trace names no profile_start_time/profile_stop_time")
    # Event times count from the session's start.
    for ops in devices.values():
        for op in ops:
            op[1] = (start_ns + op[1]) / 1e9
            op[2] = op[2] / 1e9
    return {
        "start": start_ns / 1e9,
        "stop": stop_ns / 1e9,
        "devices": devices,
    }


# ---------------------------------------------------------------------------
# arithmetic on [name, start_s, duration_s] lists
# ---------------------------------------------------------------------------


def union(ops: list) -> list[tuple[float, float]]:
    """The merged intervals in which some operation ran."""
    out: list[list[float]] = []
    for _name, t0, dur in sorted(ops, key=lambda o: o[1]):
        if out and t0 <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t0 + dur)
        else:
            out.append([t0, t0 + dur])
    return [(a, b) for a, b in out]


def busy_s(ops: list) -> float:
    return sum(b - a for a, b in union(ops))


def gaps(ops: list, start: float, stop: float) -> list[tuple[float, float]]:
    """The idle stretches of ``[start, stop]``, longest first."""
    out, at = [], start
    for a, b in union(ops):
        if a > at:
            out.append((at, min(a, stop)))
        at = max(at, b)
    if stop > at:
        out.append((at, stop))
    return sorted(out, key=lambda g: g[0] - g[1])


def top_ops(ops: list, n: int = 10) -> list[list]:
    total: dict[str, float] = {}
    for name, _t0, dur in ops:
        total[name] = total.get(name, 0.0) + dur
    return [[k[:64], v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]


if __name__ == "__main__":
    json.dump(read_trace(sys.argv[1]), sys.stdout)
