"""CI smoke for device-fault tolerance (``make device-chaos-smoke``).

Runs on the virtual 8-device CPU mesh (same re-exec harness as
multichip-smoke) and asserts, in one process, the ISSUE-15 chaos
contract for EACH injected fault shape — oom, error, and hang:

* under a persistent device fault, a mixed Count/Range/TopN/Sum storm
  keeps answering BYTE-IDENTICALLY to the pre-fault answers (host
  fallback over the authoritative planes);
* the device quarantines within the configured threshold
  (``/debug/health``-shaped snapshot shows a quarantined path and the
  node-level degraded flag);
* a hang inside the mesh-collective launch trips the launch WATCHDOG
  (``device.watchdogTrips`` > 0) instead of wedging the process — the
  storm query that hit it still answers, bounded by the watchdog;
* clearing the fault heals the device through a half-open probe (state
  back to healthy, degraded flag off) and the device path serves
  again.

Deterministic, seconds, no accelerator required — BLOCKING in
check.yml alongside chaos-smoke/resize-smoke.
"""

from __future__ import annotations

import os
import sys
import tempfile
import time

if not os.environ.get("_DEVICE_CHAOS_SMOKE_REEXEC"):
    flags = os.environ.get("XLA_FLAGS", "")
    flags = " ".join(
        f for f in flags.split()
        if "xla_force_host_platform_device_count" not in f
    )
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count=8".strip()
    )
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["_DEVICE_CHAOS_SMOKE_REEXEC"] = "1"
    os.execv(sys.executable, [sys.executable] + sys.argv)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

N_SLICES = 5
OPEN_MS = 250.0
WATCHDOG_MS = 400.0


def log(msg: str) -> None:
    print(f"[device-chaos-smoke] {msg}", file=sys.stderr, flush=True)


def fail(msg: str) -> "int":
    print(f"FAIL: {msg}", file=sys.stderr, flush=True)
    return 1


def build(tmp: str):
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.ops.bitplane import SLICE_WIDTH

    holder = Holder(os.path.join(tmp, "data"))
    holder.open()
    idx = holder.create_index("i")
    f = idx.create_frame("f", cache_size=64)
    for row in range(1, 5):
        for s in range(N_SLICES):
            for k in range(row + 3):
                f.set_bit(
                    "standard", row, s * SLICE_WIDTH + (row * 37 + k * 911) % SLICE_WIDTH
                )
    f.set_options(range_enabled=True)
    f.create_field("v", -100, 100)
    for s in range(N_SLICES):
        for k in range(12):
            col = s * SLICE_WIDTH + k * 131
            f.import_value("v", [col], [((s * 17 + k * 29) % 201) - 100])
    ft = idx.create_frame("t", cache_size=64)
    for row in range(5):
        for s in range(N_SLICES):
            for k in range(6 + row):
                ft.set_bit(
                    "standard", row, s * SLICE_WIDTH + (row * 53 + k * 197) % SLICE_WIDTH
                )
    return holder


QUERIES = [
    "Count(Intersect(Bitmap(rowID=1, frame=f), Bitmap(rowID=2, frame=f)))",
    "Count(Union(Bitmap(rowID=1, frame=f), Bitmap(rowID=3, frame=f)))",
    "Count(Difference(Bitmap(rowID=2, frame=f), Bitmap(rowID=4, frame=f)))",
    "Count(Range(frame=f, v > 10))",
    "Count(Range(frame=f, v <= -5))",
    "Count(Range(frame=f, v >< [-50, 50]))",
    "Sum(frame=f, field=v)",
    "Min(frame=f, field=v)",
    "Max(frame=f, field=v)",
    "TopN(Bitmap(rowID=0, frame=t), frame=t, n=3)",
    "TopN(frame=t, n=2)",
]


def canon(result):
    if hasattr(result, "bits"):
        return ("bits", tuple(result.bits()))
    if isinstance(result, list):
        return ("pairs", tuple((p.id, p.count) for p in result))
    if hasattr(result, "value"):
        return ("valcount", int(result.value), int(result.count))
    if result is None:
        return ("none",)
    return ("val", int(result))


def run_storm(ex, parse_string):
    return [canon(ex.execute("i", parse_string(q))[0]) for q in QUERIES]


def main() -> int:
    import jax

    from pilosa_tpu.cluster.topology import new_cluster
    from pilosa_tpu.device.health import (
        COLLECTIVE,
        STATE_HEALTHY,
        STATE_QUARANTINED,
        DeviceHealth,
    )
    from pilosa_tpu.exec import Executor
    from pilosa_tpu.exec.coalesce import CoalesceScheduler
    from pilosa_tpu.pql.parser import parse_string
    from pilosa_tpu.testing import faults

    n_dev = len(jax.devices())
    log(f"backend={jax.default_backend()} devices={n_dev}")
    if n_dev < 2:
        return fail("expected the virtual 8-device mesh")

    tmp = tempfile.mkdtemp(prefix="device-chaos-smoke-")
    holder = build(tmp)
    cluster = new_cluster(1)
    host = cluster.nodes[0].host

    # Baseline answers on a healthy device path.
    base_ex = Executor(holder, host=host, cluster=cluster)
    try:
        want = run_storm(base_ex, parse_string)
    finally:
        base_ex.close()
    log(f"baseline: {len(want)} mixed queries answered on-device")

    rc = 0
    for kind in ("oom", "error", "hang"):
        dh = DeviceHealth(
            quarantine_threshold=2,
            open_ms=OPEN_MS,
            probe_successes=1,
            watchdog_ms=WATCHDOG_MS,
        )
        co = CoalesceScheduler(max_wait_us=50_000, health=dh)
        ex = Executor(
            holder, host=host, cluster=cluster, coalescer=co, device_health=dh
        )
        try:
            if kind == "hang":
                # ONE hang INSIDE the collective dispatch (the
                # watchdogged site): an injected wedge well past the
                # watchdog deadline — the tripped query must still
                # answer (per-slice fallback), bounded by the watchdog
                # rather than the full wedge.
                faults.install(
                    "device.launch:kind=hang,path=collective,times=1,"
                    f"delay-ms={WATCHDOG_MS * 2:.0f}"
                )
            else:
                faults.install(f"device.launch:kind={kind}")

            trips_before = dh.snapshot()["watchdogTrips"]
            t0 = time.monotonic()
            for round_i in range(2):
                got = run_storm(ex, parse_string)
                if got != want:
                    rc |= fail(
                        f"kind={kind} round={round_i}: answers diverged "
                        "under injected fault"
                    )
            storm_s = time.monotonic() - t0
            snap = dh.snapshot()
            if kind == "hang":
                cpath = snap["paths"].get(COLLECTIVE, {})
                if snap["watchdogTrips"] <= trips_before:
                    rc |= fail(f"hang: watchdog never tripped: {snap}")
                elif cpath.get("quarantines", 0) < 1 or (
                    cpath.get("failures", {}).get("hang", 0) < 1
                ):
                    rc |= fail(
                        f"hang: collective path never quarantined: {snap}"
                    )
                else:
                    # The storm outlives the open window, so the path
                    # may ALREADY have healed through its probe by now
                    # — quarantines>=1 proves the trip quarantined it.
                    log(
                        f"kind=hang: watchdog tripped "
                        f"({snap['watchdogTrips'] - trips_before} trip(s)), "
                        "collective quarantined "
                        f"(state now {cpath.get('state')}), storm "
                        f"{storm_s:.2f}s (process never wedged)"
                    )
            else:
                if not snap["degraded"]:
                    rc |= fail(
                        f"kind={kind}: node never degraded: {snap}"
                    )
                quarantined = [
                    p
                    for p, st in snap["paths"].items()
                    if st["state"] == STATE_QUARANTINED
                ]
                if not quarantined:
                    rc |= fail(f"kind={kind}: nothing quarantined: {snap}")
                kinds_seen = {
                    k
                    for st in snap["paths"].values()
                    for k in st.get("failures", {})
                }
                if kind not in kinds_seen:
                    rc |= fail(
                        f"kind={kind}: classifier never saw it: {snap}"
                    )
                log(
                    f"kind={kind}: byte-identical under fault, "
                    f"quarantined={quarantined}"
                )

            # Recovery: clear the rules, wait out the open window (and
            # for a hang, the abandoned sleeper), probe, heal.
            faults.clear()
            time.sleep(
                (OPEN_MS / 1000.0) + (WATCHDOG_MS * 2 / 1000.0 + 0.2 if kind == "hang" else 0.1)
            )
            got = run_storm(ex, parse_string)
            if got != want:
                rc |= fail(f"kind={kind}: answers diverged after heal")
            snap = dh.snapshot()
            bad = {
                p: st["state"]
                for p, st in snap["paths"].items()
                if st["state"] != STATE_HEALTHY
            }
            if bad:
                # One more storm gives every touched path its probe.
                got = run_storm(ex, parse_string)
                snap = dh.snapshot()
                bad = {
                    p: st["state"]
                    for p, st in snap["paths"].items()
                    if st["state"] != STATE_HEALTHY
                }
            if bad or snap["degraded"]:
                rc |= fail(f"kind={kind}: did not heal: {snap}")
            else:
                log(f"kind={kind}: healed through half-open probe")
        finally:
            faults.clear()
            ex.close()
            co.close()
            dh.close()

    holder.close()
    if os.environ.get("PILOSA_LOCK_CHECK"):
        # Runtime lock-order validation (PR 8): the watchdog runner's
        # collective-mutex acquisitions observed during the storms must
        # be consistent with the static lock graph (the analyze.toml
        # watchdog callback edges complete it).
        from pilosa_tpu.analyze import runtime as lock_check

        problems = lock_check.verify()
        print(lock_check.report().splitlines()[0], file=sys.stderr)
        if problems:
            for p in problems:
                print("lock-check DISAGREEMENT:", p, file=sys.stderr)
            return 1
        log("lock-check ok: runtime order consistent with static graph")
    if rc == 0:
        print(
            "OK: oom/error/hang storms byte-identical via host fallback, "
            "quarantine + watchdog + half-open heal all observed"
        )
    return rc


if __name__ == "__main__":
    sys.exit(main())
