#!/usr/bin/env python3
"""One run of one benchmark cell against the served path.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell comes from ``BENCHMARK.json``; its configuration, its traffic
mix and each per-layer metric are files found by name under
``benchmarks/`` (``README.md`` there says how to add one), and so is the
configuration's deployment kind (``deployments/<kind>.py``), which owns
the schema, the data, the requests, the plain reference and the control.
This process never initialises a JAX backend.  It starts one child
``python -m pilosa_tpu.cli server`` on the default configuration, creates
the kind's schema, loads its data from ``--seed`` through ``POST /import``
or ``/import-value``, warms the mix's own query shapes, drives
``POST /index/<i>/query`` for ``--seconds``, stops the server, compares
every answer the window got with the kind's numpy reference, and prints
one JSON line.  With no TPU, or
another number of chips than the cell asks for, it exits 2 and prints no
result.
"""

from __future__ import annotations

import argparse
import glob
import importlib.util
import json
import os
import re
import shutil
import subprocess
import sys
import tarfile
import tempfile
import threading
import time
import traceback
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field

T_START = time.monotonic()

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)  # pilosa_tpu.net.client, for the load alone

import metrics as metrics_mod  # noqa: E402
import xplane  # noqa: E402
from server import DEADLINE_MS, LOG_MUST_NOT_HAVE, HarnessError, Server  # noqa: E402
from traffic import Load, Record  # noqa: E402

LOAD_THREADS = 4
# A traced run keeps every trace of its window: the server's ring holds
# 64 by default.
TRACE_RING = 200_000
# The profile covers this share of the window (the server clamps it to
# 60 s), from a tenth of the way in.
PROFILE_SHARE = 0.25
PROGRAMS = "pilosa_exec_programCache_entries"
# The kind of a configuration whose file names none, and what a kind's
# module has to hold (``README.md``, "A deployment kind").
DEFAULT_KIND = "two-row-count"
KIND_PARTS = ("schema", "Reference", "Traffic", "normalise", "CONTROLS", "SITES")


def say(msg: str) -> None:
    print(f"[{time.monotonic() - T_START:7.1f}] {msg}", file=sys.stderr, flush=True)


def read_json(path: str):
    with open(path) as f:
        return json.load(f)


@dataclass
class Rig:
    """Where a run differs from the command line's: the rehearsal tests
    alone set any of this (a CPU in the chip's place, a server broken
    underneath, fixtures for the files)."""

    platform: str = "tpu"
    server_argv: list[str] | None = None
    extra_env: dict = field(default_factory=dict)
    root: str = ROOT
    mix_dir: str = os.path.join(HERE, "traffic")
    kind_dir: str = os.path.join(HERE, "deployments")


def load_kind(name: str, kind_dir: str = Rig.kind_dir):
    """The module ``<kind_dir>/<name>.py``, found by name as
    ``metrics.load_reducer`` finds a reducer."""
    path = os.path.join(kind_dir, name + ".py")
    if not re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}", name) or not os.path.exists(path):
        raise HarnessError(f"no deployment kind {name!r}: no file {path}")
    spec = importlib.util.spec_from_file_location(
        "deployment_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [part for part in KIND_PARTS if not hasattr(mod, part)]
    if missing:
        raise HarnessError(f"deployment kind {name!r} lacks {', '.join(missing)}")
    return mod


class Cell:
    """A cell of ``BENCHMARK.json`` with the files it names."""

    def __init__(self, bench: dict, workload: str, rig: Rig | None = None):
        rig = rig or Rig()
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise HarnessError(
                f"no workload {workload!r}; BENCHMARK.json has {sorted(cells)}"
            )
        self.spec = cells[workload]
        self.chips = int(self.spec["chips"])
        cfg = next(c for c in bench["configs"] if c["name"] == self.spec["config"])
        self.config = read_json(os.path.join(rig.root, cfg["file"]))
        self.kind = load_kind(self.config.get("kind", DEFAULT_KIND), rig.kind_dir)
        mix = os.path.join(rig.mix_dir, self.spec["traffic"] + ".json")
        if not os.path.exists(mix):
            raise HarnessError(f"no traffic mix {mix}")
        self.mix = read_json(mix)
        self.end_to_end = [
            m for m in bench["end_to_end"]
            if "workloads" not in m or workload in m["workloads"]
        ]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [
            m for m in bench["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in reported)
        ]


class Run:
    def __init__(self, cell: Cell, seed: int, seconds: float, traced: bool,
                 rig: Rig):
        self.cell, self.seed, self.seconds, self.traced = cell, seed, seconds, traced
        self.platform = rig.platform
        self.kind = cell.kind
        self.ref = cell.kind.Reference(cell.config, seed)
        self.traffic = cell.kind.Traffic(cell.mix, cell.config, seed)
        self.work = tempfile.mkdtemp(prefix="pilosa-bench-")
        env = dict(rig.extra_env)
        if traced:
            env["PILOSA_OBS_TRACE_RING"] = str(TRACE_RING)
        self.server = Server(os.path.join(self.work, "data"),
                             os.path.join(self.work, "server.log"), env,
                             rig.server_argv)
        self.setup: dict[str, float] = {}
        self.device: dict = {}
        self.profile_reply: dict | None = None

    # -- set-up -------------------------------------------------------------

    def boot(self) -> None:
        t0 = time.monotonic()
        os.makedirs(self.server.data_dir)
        self.server.start()
        self.server.wait_listening(timeout=300)
        self.device = self.server.device()
        self.setup["boot_s"] = time.monotonic() - t0
        say(f"server up in {self.setup['boot_s']:.1f} s on {self.device}")

    def device_is_the_cells(self) -> bool:
        return (self.device["platform"] == self.platform
                and self.device["count"] == self.cell.chips)

    def load(self) -> None:
        from pilosa_tpu.net.client import InternalClient

        t0 = time.monotonic()
        host = f"127.0.0.1:{self.server.port}"
        client = InternalClient(host, timeout=120.0)
        for index in self.kind.schema(self.cell.config):
            client.create_index(index["name"], index.get("options"))
            for frame in index["frames"]:
                client.create_frame(index["name"], frame["name"], frame.get("options"))
                for fld in frame.get("fields", ()):
                    client.create_field(index["name"], frame["name"], fld["name"],
                                        fld["min"], fld["max"])

        def one(unit) -> None:
            # Generation runs here, inside the load's threads.
            u = self.ref.make(unit)
            to = InternalClient(host, timeout=120.0)
            if u["route"] == "import":
                to.import_bits(u["index"], u["frame"], u["slice"],
                               (u["rows"], u["cols"]))
            elif u["route"] == "import-value":
                to.import_value(u["index"], u["frame"], u["field"], u["slice"],
                                u["columns"], u["values"])
            else:
                raise HarnessError(f"no load route {u['route']!r}")

        with ThreadPoolExecutor(LOAD_THREADS) as pool:
            list(pool.map(one, self.ref.units()))
        self.ref.seal()
        self.setup["load_s"] = time.monotonic() - t0
        say(f"loaded {self.ref.n_loaded} bits and values in "
            f"{self.setup['load_s']:.1f} s")

    def warm(self) -> None:
        t0 = time.monotonic()
        deadline = t0 + 600
        while True:
            pw = self.server.get_json("/debug/health").get("prewarm")
            if pw is None or pw["done"]:
                break
            if time.monotonic() > deadline:
                raise HarnessError("prewarm not done after 600 s")
            time.sleep(0.25)
        if pw and pw.get("error"):
            raise HarnessError(f"prewarm failed: {pw['error']}")
        self.setup["prewarm_wait_s"] = time.monotonic() - t0
        load = Load(self.server, self.traffic.index, traced=False)

        for reqs in self.traffic.warmup_rounds():
            for rec in load.round(reqs):
                if rec.status != 200:
                    raise HarnessError(
                        f"warm-up {rec.req.text!r} -> {rec.status} {rec.answer!r}"
                    )
        self.setup["warm_s"] = time.monotonic() - t0
        say(f"warm in {self.setup['warm_s']:.1f} s "
            f"(prewarm wait {self.setup['prewarm_wait_s']:.1f} s)")

    # -- the window ---------------------------------------------------------

    def counters(self) -> dict:
        health = self.server.get_json("/debug/health").get("device") or {}
        return {
            "perf": self.server.get_json("/debug/perf")["sites"],
            "metrics": self.server.metrics(),
            "health": health,
        }

    def _profile(self) -> None:
        time.sleep(0.1 * self.seconds)
        seconds = max(1.0, PROFILE_SHARE * self.seconds)
        status, data = self.server.request("GET", f"/debug/profile?seconds={seconds:g}")
        if status == 200:
            self.profile_reply = json.loads(data)
        else:
            say(f"/debug/profile -> {status}: {data[:200]!r}")

    def window(self) -> dict:
        before = self.counters()
        self.setup["setup_s"] = time.monotonic() - T_START
        load = Load(self.server, self.traffic.index, self.traced)
        prof = threading.Thread(target=self._profile) if self.traced else None
        if prof:
            prof.start()
        w0, w1 = load.run(self.traffic, self.seconds)
        if prof:
            prof.join()
        say(f"window: {len(load.records)} requests in {w1 - w0:.2f} s")
        after = self.counters()
        grew = {k[len(PROGRAMS):] or "all": v - before["metrics"].get(k, 0.0)
                for k, v in after["metrics"].items() if k.startswith(PROGRAMS)}
        say(f"programs compiled in the window: {({k: v for k, v in grew.items() if v} or 0)}")
        ev = {
            "window": (w0, w1),
            "raw_records": load.records,
            "perf": {"before": before["perf"], "after": after["perf"]},
            "metrics": {"before": before["metrics"], "after": after["metrics"]},
            "health": {"before": before["health"], "after": after["health"]},
            "hbm": self.server.get_json("/debug/hbm"),
            "traces": [],
            "setup": self.setup, "device": self.device,
            "config": self.cell.config, "deadline_ms": float(DEADLINE_MS),
        }
        if self.traced:
            mine = {r.trace_id for r in load.records}
            ev["traces"] = [
                t for t in self.server.get_json("/debug/traces")["traces"]
                if t["trace_id"] in mine
            ]
        return ev

    def memory_peak_bytes(self, ev: dict) -> int:
        """The fullest chip.  The server publishes no allocator peak: the
        larger of the pool's high-water mark of resident bytes and the
        allocator's ``bytes_in_use`` gauge as last published."""
        pool = [d.get("max_resident_bytes", 0) for d in ev["hbm"].get("devices", [])]
        gauge = [v for k, v in ev["metrics"]["after"].items()
                 if k.startswith("pilosa_device_") and k.endswith("_hbm_bytes_in_use")]
        return int(max(pool + gauge + [0]))

    # -- after the window ---------------------------------------------------

    def unpack_profile(self) -> str | None:
        if not self.profile_reply:
            return None
        out = os.path.join(self.work, "profile")
        with tarfile.open(self.profile_reply["trace"]) as tf:
            tf.extractall(out, filter="data")
        found = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)
        return found[0] if found else None

    def read_profile(self, pb: str | None) -> dict | None:
        """In a child held to the CPU, once the server has let the chips
        go: this process never loads a backend."""
        if pb is None:
            return None
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, os.path.join(HERE, "xplane.py"), pb],
            env=env, capture_output=True, text=True, timeout=240,
        )
        if proc.returncode != 0:
            say(f"xplane.py failed: {proc.stderr[-500:]}")
            return None
        return json.loads(proc.stdout)

    def compare(self, ev: dict) -> dict:
        """Every answer the window got against the reference
        (:func:`compare_answers`), and the server's own word on whether
        the device produced them.  Each number sits beside its limit."""
        ev["records"], compared = compare_answers(ev.pop("raw_records"), self.ref,
                                                  self.kind.normalise)

        def site(when, name, key="launches"):
            return ev["perf"][when].get(name, {}).get(key, 0)

        hosteval = site("after", "hosteval") - site("before", "hosteval")
        launches = sum(site("after", s) - site("before", s) for s in self.kind.SITES)
        h = ev["health"]["after"]
        paths = h.get("paths", {}).values()
        faults = (
            int(bool(h.get("degraded")))
            + sum(st.get("state") != "healthy" for st in paths)
            + sum(_count(st.get("failures")) for st in paths)
            + int(h.get("watchdogTrips", 0))
            + int(ev["metrics"]["after"].get("pilosa_device_launch_retries_total", 0))
        )
        log = self.server.log_text()
        return {
            **compared,
            "hosteval_launches": {"value": hosteval, "limit": 0},
            "device_launches": {"value": launches, "at_least": 1},
            "device_faults": {"value": faults, "limit": 0},
            "log_failures": {
                "value": sum(1 for s in LOG_MUST_NOT_HAVE if s in log), "limit": 0,
            },
        }

    def readback(self, ev: dict, compared: dict) -> None:
        """Every acknowledged write, applied to the reference and read
        back through the kind's own texts before the server stops."""
        for r in ev["raw_records"]:
            if r.req.kind == "write" and r.status == 200:
                self.ref.apply(r.req.key)
        wrong = 0
        for req in self.ref.readback():
            status, data = self.server.request(
                "POST", f"/index/{self.traffic.index}/query", req.text.encode())
            got = json.loads(data).get("results", [None])[0] if status == 200 else None
            wrong += got is None or self.kind.normalise(got) != self.ref.answer(req.key)
        compared["writes_not_read_back"] = {"value": wrong, "limit": 0}

    def log_tail(self) -> str:
        try:
            return self.server.log_text()[-3000:]
        except OSError:
            return ""

    def close(self) -> None:
        self.server.kill()
        shutil.rmtree(self.work, ignore_errors=True)


def _count(failures) -> int:
    """``/debug/health`` gives a path's failures by kind, or nothing."""
    if isinstance(failures, dict):
        return sum(failures.values())
    return int(bool(failures))


def compare_answers(raw: list[Record], ref, normalise) -> tuple[list[dict], dict]:
    """The comparison that decides whether the answers are right: every
    read the window got, put by the kind's ``normalise`` into the form
    its reference answers in and held with ``==`` to ``ref.answer``.  A
    kind has no tolerance to set, and the limits are the same for all.
    Returns the records as the reducers read them and the numbers
    compared, each beside its limit.  The control (``control.py``) puts
    a broken reference's answers through this same function."""
    records, wrong, unanswered = [], 0, 0
    cache: dict[tuple, object] = {}
    for r in raw:
        ok = r.status == 200 and r.answer is not None
        correct = False
        if not ok:
            unanswered += 1
        elif r.req.kind == "read":
            if r.req.key not in cache:
                cache[r.req.key] = ref.answer(r.req.key)
            correct = normalise(r.answer) == cache[r.req.key]
            wrong += not correct
        else:
            correct = True  # an acknowledged write; readback() judges it
        records.append({
            "kind": r.req.kind, "text": r.req.text, "client": r.client,
            "sent": r.sent, "done": r.done, "latency_ms": r.latency_s * 1e3,
            "late_ms": r.late_s * 1e3, "ok": ok, "correct": correct,
            "trace_id": r.trace_id,
        })
    return records, {
        "answers_compared": {"value": len(records) - unanswered, "at_least": 1},
        "wrong_answers": {"value": wrong, "limit": 0},
        "unanswered": {"value": unanswered, "limit": 0},
    }


def is_correct(compared: dict) -> bool:
    return all(
        c["value"] <= c["limit"] if "limit" in c else c["value"] >= c["at_least"]
        for c in compared.values()
    )


def breakdown(ev: dict) -> dict | None:
    """The device operations that took most time, and the idle time of
    the busiest device by the server span open at each gap's middle."""
    prof = ev.get("profile")
    if not prof or not prof["devices"]:
        return None
    _name, ops = max(prof["devices"].items(), key=lambda kv: xplane.busy_s(kv[1]))
    # Innermost first: the shortest span open at a moment owns it.
    spans = sorted(
        ((s["start"], s["start"] + (s["duration_ms"] or 0.0) / 1e3, s["name"])
         for t in ev["traces"] for s in t["spans"]),
        key=lambda s: s[1] - s[0],
    )
    by_span: dict[str, float] = {}
    for a, b in xplane.gaps(ops, prof["start"], prof["stop"]):
        mid = (a + b) / 2
        owner = next((n for s0, s1, n in spans if s0 <= mid <= s1), "no_request")
        by_span[owner] = by_span.get(owner, 0.0) + (b - a)
    return {
        "device_ops": xplane.top_ops(ops),
        "idle_gaps": [[k, v] for k, v in
                      sorted(by_span.items(), key=lambda kv: -kv[1])[:10]],
    }


def jax_backend_in_this_process() -> bool:
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def run_cell(bench: dict, workload: str, seed: int, seconds: float, traced: bool,
             rig: Rig | None = None) -> tuple[int, dict | None]:
    """``(exit code, result line or None)``."""
    rig = rig or Rig()
    cell = Cell(bench, workload, rig)
    run = Run(cell, seed, seconds, traced, rig)
    try:
        run.boot()
        if not run.device_is_the_cells():
            say(f"the cell needs {cell.chips} x {rig.platform}; the server runs on "
                f"{run.device}: no result")
            return 2, None
        run.load()
        run.warm()
        ev = run.window()
        memory_peak = run.memory_peak_bytes(ev)
        written: dict = {}
        if any(r.req.kind == "write" for r in ev["raw_records"]):
            run.readback(ev, written)
        pb = run.unpack_profile() if traced else None
        rc = run.server.stop()
        say(f"server stopped with {rc}")
        compared = run.compare(ev)
        compared.update(written)
        ev["profile"] = run.read_profile(pb)
    except BaseException:
        say("no result; the server's log ends:\n" + run.log_tail())
        raise
    finally:
        log_tail = run.log_tail()
        run.close()
    if jax_backend_in_this_process():
        raise HarnessError("the parent process initialised a JAX backend")

    out: dict = {}
    if traced:
        for m in cell.per_layer:
            value = metrics_mod.layer_metric(m["name"], ev)
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            out[m["name"]] = {
                "value": metrics_mod.END_TO_END[m["name"]](ev), "unit": m["unit"],
            }
    device = dict(run.device, memory_peak_bytes=memory_peak)
    prof = ev.get("profile")
    if traced and prof and prof["devices"]:
        device["busy_s"] = sum(
            xplane.busy_s(ops) for ops in prof["devices"].values()
        ) / len(prof["devices"])
        device["window_s"] = prof["stop"] - prof["start"]
    correct = is_correct(compared)
    line = {
        "correct": correct,
        "attempted": len(ev["records"]),
        "failed": sum(1 for r in ev["records"] if not r["ok"]),
        "metrics": out,
        "device": device,
    }
    if traced:
        bd = breakdown(ev)
        if bd:
            line["breakdown"] = bd
    line["compared"] = compared
    if not correct:
        say("NOT CORRECT; the server's log ends:\n" + log_tail)
    for name, c in compared.items():
        bound = f"limit {c['limit']}" if "limit" in c else f"at least {c['at_least']}"
        print(f"compared {name} = {c['value']} ({bound})", file=sys.stderr, flush=True)
    return 0, line


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        print(f"benchmarks/run.py: JAX_PLATFORMS={platforms!r} hides the TPU: "
              "no result", file=sys.stderr)
        return 2
    if not os.path.isdir(os.path.join(ROOT, "pilosa_tpu")):
        print("benchmarks/run.py: no pilosa_tpu/ beside benchmarks/: nothing "
              "to measure", file=sys.stderr)
        return 2
    try:
        bench = read_json(os.path.join(ROOT, "BENCHMARK.json"))
        rc, line = run_cell(bench, args.workload, args.seed, args.seconds,
                            bool(args.trace))
    except HarnessError as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return 1
    except Exception:  # noqa: BLE001 — the boundary: no result line, code 1
        traceback.print_exc()
        return 1
    if line is not None:
        print(json.dumps(line), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
