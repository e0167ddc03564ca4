"""A run of ``benchmarks/run.py`` end to end on the CPU: 4 slices x 8
rows, the shipped mixes, the real server child.  The harness's look for
a chip is skipped (``platform="cpu"``); everything after it is the code
a chip run executes.  Seconds each; nothing here waits on a timer.
"""

import json
import os
import subprocess
import sys

import pytest

import run
from conftest import BENCH, FIXTURES, HERE, ROOT

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_METRICS = {"device.count_roofline", "device.idle_share"}


def tiny_bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {
        "tiny.count-distinct": ("tiny", "count-distinct", 1),
        "tiny.count-repeat": ("tiny", "count-repeat", 1),
        "tiny.rw-mix": ("tiny", "rw-mix-tiny", 1),
        "tiny-wide.count-distinct": ("tiny-wide", "count-distinct", 4),
        "tiny-wide.count-repeat": ("tiny-wide", "count-repeat", 4),
        "ranked-bsi-tiny.topn-sum": ("ranked-bsi-tiny", "topn-sum-tiny", 1),
    }
    bench["configs"] = [
        {"name": n, "file": f"benchmarks/tests/fixtures/{n}.json"}
        for n in ("tiny", "tiny-wide", "ranked-bsi-tiny")
    ]
    bench["workloads"] = [
        {"name": k, "config": c, "traffic": t, "chips": n}
        for k, (c, t, n) in cells.items()
    ]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            only_repeat = len(m["workloads"]) == 1
            m["workloads"] = [k for k in cells if not only_repeat or "repeat" in k]
    return bench


def cpu_env(devices: int) -> dict:
    return {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
        "PILOSA_TPU_COMPILATION_CACHE_DIR": "off",
    }


def rehearse(cell, traced, devices, seed=2_147_483_700, seconds=1.0,
             server_argv=None, env=None, mix_dir=run.Rig.mix_dir,
             kind_dir=run.Rig.kind_dir):
    rig = run.Rig(platform="cpu", server_argv=server_argv,
                  extra_env={**cpu_env(devices), **(env or {})}, mix_dir=mix_dir,
                  kind_dir=kind_dir)
    return run.run_cell(tiny_bench(), cell, seed, seconds, traced, rig)


def rehearse_the_fixture_kind(traced=False, server_argv=None):
    """The kind that lives under ``fixtures/`` alone: its module, its
    configuration and its mix are all found there."""
    return rehearse("ranked-bsi-tiny.topn-sum", traced, 1, server_argv=server_argv,
                    mix_dir=os.path.join(FIXTURES, "traffic"),
                    kind_dir=os.path.join(FIXTURES, "deployments"))


@pytest.mark.parametrize("cell,traced,devices", [
    ("tiny.count-distinct", False, 1),
    ("tiny.count-repeat", True, 1),
    ("tiny-wide.count-distinct", True, 4),
    ("tiny-wide.count-repeat", False, 4),
])
def test_a_good_run(cell, traced, devices):
    rc, line = rehearse(cell, traced, devices)
    assert rc == 0
    line = json.loads(json.dumps(line))  # it serialises
    assert set(line) - {"breakdown", "compared"} == CONTRACT_KEYS
    assert list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == devices
    assert line["compared"]["wrong_answers"] == {"value": 0, "limit": 0}
    assert line["compared"]["answers_compared"]["value"] == line["attempted"]
    names = set(line["metrics"])
    # A run labelled cpu carries no device metric.
    assert not names & DEVICE_METRICS
    assert "busy_s" not in line["device"]
    if traced:
        assert {"exec.plan_ms", "exec.batch_cache_hit_share", "setup.load_s",
                "http.outside_execute_ms", "exec.coalesce_occupancy"} <= names
        hit = line["metrics"]["exec.batch_cache_hit_share"]["value"]
        assert hit == (100.0 if "repeat" in cell else 0.0)
        assert line["metrics"]["client.latency_p99_ms"]["value"] > 0
        assert line["metrics"]["device.window_new_programs"]["value"] >= 0
    else:
        assert names == {"answers_per_s", "setup_s"}
        assert all(m["value"] > 0 for m in line["metrics"].values())


@pytest.mark.parametrize("traced", [False, True])
def test_a_kind_the_harness_has_never_seen_runs_with_no_edit(traced):
    """A ranked-cache frame and a BSI field through ``/import-value``,
    TopN and Sum(Range) reads: a list-valued and a dict-valued answer."""
    rc, line = rehearse_the_fixture_kind(traced)
    assert rc == 0
    line = json.loads(json.dumps(line))
    assert set(line) - {"breakdown", "compared"} == CONTRACT_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 4  # each of the four texts at least once
    c = line["compared"]
    assert c["answers_compared"]["value"] == line["attempted"]
    assert c["wrong_answers"] == {"value": 0, "limit": 0}
    assert c["hosteval_launches"] == {"value": 0, "limit": 0}
    # the kind's own sites: TopN's scorer and the BSI aggregate both rode one
    assert c["device_launches"]["value"] >= 1
    if traced:
        assert {"client.latency_p99_ms", "setup.load_s"} <= set(line["metrics"])
    else:
        assert set(line["metrics"]) == {"answers_per_s", "setup_s"}


def test_the_fixture_kinds_altered_answer_is_not_correct():
    argv = [sys.executable, os.path.join(HERE, "broken_server.py"),
            "answer_altered", "server"]
    rc, line = rehearse_the_fixture_kind(server_argv=argv)
    assert rc == 0
    assert line["correct"] is False
    assert line["compared"]["wrong_answers"]["value"] > 0


def test_open_loop_with_writes_reads_every_acked_write_back():
    rc, line = rehearse("tiny.rw-mix", False, 1,
                        mix_dir=os.path.join(FIXTURES, "traffic"))
    assert rc == 0 and line["correct"] is True
    assert line["compared"]["writes_not_read_back"] == {"value": 0, "limit": 0}
    assert line["attempted"] == 40  # 40/s for 1 s, whatever the replies did


@pytest.mark.parametrize("fault,cell,devices", [
    ("answer_altered", "tiny.count-repeat", 1),
    ("answer_altered", "tiny.count-distinct", 1),
    ("half_left_out", "tiny.count-distinct", 1),
    ("exchange_left_out", "tiny-wide.count-distinct", 4),
])
def test_a_broken_timed_path_is_not_correct(fault, cell, devices):
    argv = [sys.executable, os.path.join(HERE, "broken_server.py"), fault, "server"]
    rc, line = rehearse(cell, False, devices, server_argv=argv)
    assert rc == 0
    assert line["correct"] is False
    assert line["compared"]["wrong_answers"]["value"] > 0


def test_an_answer_from_hosteval_is_not_a_good_run():
    rc, line = rehearse("tiny.count-distinct", False, 1,
                        env={"PILOSA_FAULTS": "device.launch:kind=oom"})
    assert rc == 0
    assert line["correct"] is False
    c = line["compared"]
    assert c["hosteval_launches"]["value"] > 0 or c["device_faults"]["value"] > 0
    # The answers themselves were right: only the device did not give them.
    assert c["wrong_answers"]["value"] == 0


def test_the_wrong_number_of_chips_prints_no_result():
    rc, line = rehearse("tiny-wide.count-distinct", False, 1)
    assert rc == 2 and line is None


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "segment-1b.count-repeat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""


def test_alone_with_its_paths_it_prints_no_result(tmp_path):
    import shutil

    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "segment-1b.count-repeat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
