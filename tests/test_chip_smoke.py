"""Rehearsals of ``chip_smoke.py`` on the CPU, at a few slices, behind
its explicit ``--cpu-rehearsal`` argument — the script's own logic is
what is under test here; what it proves about the chip only a chip run
can say."""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _env(tmp_path, name: str, devices: int, **extra) -> dict:
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    # A cache of its own: the smoke compares the cache's entries across
    # its two boots, and the other rehearsal writes entries meanwhile.
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / f"cache-{name}")
    env.update(extra)
    return env


def _start(tmp_path, name: str, env: dict) -> subprocess.Popen:
    return subprocess.Popen(
        [
            sys.executable, SMOKE, "--cpu-rehearsal", "--slices", "4",
            "--out", str(tmp_path / name),
        ],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )


def _result(proc: subprocess.Popen) -> tuple[int, dict, str]:
    """The full report (the line before last).  The last line is the
    verdict, held here to the driver's contract: exactly ``ok`` and
    ``device``, the device exactly ``platform``, ``kind``, ``count``."""
    out, _ = proc.communicate(timeout=300)
    lines = out.strip().splitlines()
    assert len(lines) >= 2, out[-3000:]
    verdict, report = json.loads(lines[-1]), json.loads(lines[-2])
    assert set(verdict) == {"ok", "device"}, verdict
    assert set(verdict["device"]) == {"platform", "kind", "count"}, verdict
    assert isinstance(verdict["ok"], bool)
    assert isinstance(verdict["device"]["platform"], str)
    assert isinstance(verdict["device"]["kind"], str)
    assert type(verdict["device"]["count"]) is int
    assert verdict == {"ok": report["ok"], "device": report["device"]}
    return proc.returncode, report, out


def test_rehearsal_passes_clean_and_fails_on_host_fallback(tmp_path):
    """Run together (they share nothing): a clean rehearsal on one
    device, and one on a two-device mesh whose first launches are made
    to fail, so the server answers them — correctly — from
    ``hosteval``.  The first must pass and say ``platform: cpu``; the
    second must exit non-zero although every answer was right."""
    clean = _start(tmp_path, "clean", _env(tmp_path, "clean", 1))
    faulted = _start(
        tmp_path, "faulted",
        _env(tmp_path, "faulted", 2,
             PILOSA_FAULTS="device.launch:kind=oom,times=2"),
    )
    rc, res, out = _result(clean)
    assert rc == 0 and res["ok"], out[-3000:]
    assert res["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert res["failures"] == []
    sites = res["launches"]["boot1"]
    assert sites["hosteval"] == 0 and sites["topn"] > 0
    assert sites["fused_interpreter"] > 0
    assert res["scatter"]["launches"] >= 3
    assert res["compile_cache"]["dir"] == str(tmp_path / "cache-clean")
    assert res["compile_cache"]["entries_after_boot1"] > 0
    assert res["compile_cache"]["new_entries_in_boot2"] == []
    assert list(res)[-1] == "claim" and res["claim"] is None

    rc, res, out = _result(faulted)
    assert rc == 1 and not res["ok"], out[-3000:]
    assert res["device"]["count"] == 2
    assert res["launches"]["boot1"]["hosteval"] > 0
    failed = " ".join(res["failures"])
    assert "hosteval" in failed and "device healthy" in failed
    # ...and nothing else failed: the host evaluator's answers matched
    # the oracle, which is exactly why an answer check proves nothing.
    assert len(res["failures"]) == 2, res["failures"]


def test_no_tpu_exits_at_once_with_no_result(tmp_path):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, SMOKE], env=env, capture_output=True, text=True,
        timeout=60,
    )
    assert p.returncode == 2
    assert p.stdout == ""
    assert "hides the TPU" in p.stderr


def test_alone_in_a_directory_it_fails_with_no_result(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "tpu"
    p = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=60,
    )
    assert p.returncode != 0
    assert p.stdout == ""
    assert "pilosa_tpu" in p.stderr
