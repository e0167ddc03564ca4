"""CI smoke pass over tools/load_harness.py: a tiny CPU-only open-loop
run asserting the artifact carries the goodput curve and shed counters,
and that NOTHING is shed at trivial load (an admission layer that sheds
an idle node is misconfigured, full stop).

Not a performance measurement — a wiring check that the admission
layer, the harness, and the artifact contract all hold together, so a
refactor cannot silently break the storm tier the bench trajectory
records.  Writes ``load-report.json`` at the repo root (uploaded as a
CI artifact alongside analyze-report.json).  Run via ``make
load-smoke``; wired non-blocking into check.yml.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPORT = os.path.join(REPO, "load-report.json")


def main() -> int:
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
    )
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO, "tools", "load_harness.py"),
            "--self-boot",
            "--compare",
            "--slices", "2",
            # Two fixed points: trivial load (must shed nothing) and a
            # small storm (tiny gates below make it a real overload).
            "--qps", "15,300",
            "--duration", "2",
            "--deadline-ms", "400",
            "--slo-ms", "300",
            "--point-concurrency", "2",
            "--heavy-concurrency", "1",
            "--write-concurrency", "1",
            "--queue-depth", "4",
            "--artifact", REPORT,
        ],
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    sys.stderr.write(proc.stderr[-4000:])
    if proc.returncode != 0:
        print(f"FAIL: load_harness exited {proc.returncode}", file=sys.stderr)
        return 1
    try:
        with open(REPORT) as f:
            out = json.loads(f.read())
    except (OSError, json.JSONDecodeError) as e:
        print(f"FAIL: artifact unreadable ({e})", file=sys.stderr)
        return 1

    for side in ("admission_on", "admission_off"):
        sweep = out.get(side)
        if not isinstance(sweep, dict) or not sweep.get("points"):
            print(f"FAIL: artifact missing {side} sweep: {out}", file=sys.stderr)
            return 1
        for pt in sweep["points"]:
            for key in (
                "offered_qps", "goodput_qps", "shed", "shed_rate",
                "deadline_504", "p99_ms", "ok_within_deadline",
            ):
                if key not in pt:
                    print(f"FAIL: point missing {key!r}: {pt}", file=sys.stderr)
                    return 1
    if "max_sustained_qps_at_p99_slo" not in out:
        print("FAIL: artifact missing max_sustained_qps_at_p99_slo",
              file=sys.stderr)
        return 1
    on = out["admission_on"]
    trivial = on["points"][0]
    if trivial["shed"] != 0:
        print(f"FAIL: shed at trivial load: {trivial}", file=sys.stderr)
        return 1
    if trivial["ok_within_deadline"] < trivial["sent"] * 0.9:
        print(f"FAIL: trivial load not served: {trivial}", file=sys.stderr)
        return 1
    snap = on.get("admission_snapshot")
    if not isinstance(snap, dict) or "point" not in snap:
        print(f"FAIL: artifact missing admission snapshot: {on.keys()}",
              file=sys.stderr)
        return 1
    storm = on["points"][-1]
    print(
        "load-smoke ok: trivial load shed-free "
        f"({trivial['ok_within_deadline']}/{trivial['sent']} within "
        f"deadline); storm point goodput {storm['goodput_qps']} qps, "
        f"shed {storm['shed']}; report at {REPORT}"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
