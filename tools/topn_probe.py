"""Where the seconds of a served ``TopN(src)`` go at a deployment's own
size, one request at a time (ROADMAP M5).

    python tools/topn_probe.py [--workload segment-1b.topn-src] [--seed N]
        [--srcs 3,17,40,0] [--deadline-ms 0] [--concurrent 8]

Boots the server as ``benchmarks/run.py`` does (its ``Run``: the cell's
schema and data from ``--seed`` through ``/import``), waits for prewarm,
and sends each ``TopN(Bitmap(frame, rowID=src), frame, n)`` alone, with
no deadline header unless ``--deadline-ms`` says one (the server's
default is 60 s), checks the answer against the kind's reference and
prints the request's spans from ``/debug/traces``: ``topn.prep``,
``topn.dispatch`` and the ``compile`` under it, ``topn.fetch``,
``topn.select``, and under ``stages`` their milliseconds on one line with
``topn.prep``'s ``prep_cache`` and ``build`` tags and ``topn.select``'s ``way``.  Then it stops the server
and boots it again on the same data: a new process, so the first request
there shows what the persistent compile cache saves.  Last,
``--concurrent`` distinct texts at once, as the cell's warm-up sends them.  One JSON document on
stdout, also written to ``chiprun_out/topn_probe.json``.  The parent of
the server never initialises a JAX backend.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import threading
import time
import uuid

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
sys.path.insert(0, BENCH)
sys.path.insert(1, ROOT)

import run  # noqa: E402 — benchmarks/run.py
from server import Server  # noqa: E402 — benchmarks/server.py

SPANS = ("topn.prep", "topn.score", "topn.dispatch", "compile", "topn.fetch",
         "launch", "topn.select", "execute", "query")


def wait_prewarm(server: Server, timeout: float = 600.0) -> float:
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        pw = server.get_json("/debug/health").get("prewarm")
        if pw is None or pw["done"]:
            return time.monotonic() - t0
        time.sleep(0.25)
    raise run.HarnessError("prewarm not done")


def ask(server: Server, index: str, text: str, deadline_ms: int) -> dict:
    """One request and its spans."""
    trace_id = uuid.uuid4().hex
    headers = {"X-Trace-Id": trace_id}
    if deadline_ms:
        headers["X-Deadline-Ms"] = str(deadline_ms)
    conn = server.connect()
    t0 = time.monotonic()
    try:
        conn.request("POST", f"/index/{index}/query", body=text.encode(),
                     headers=headers)
        resp = conn.getresponse()
        data = resp.read()
    finally:
        conn.close()
    out = {"text": text, "status": resp.status,
           "wall_s": round(time.monotonic() - t0, 3), "trace_id": trace_id}
    if resp.status == 200:
        out["answer"] = json.loads(data).get("results", [None])[0]
    else:
        out["body"] = data[:600].decode("utf-8", "replace")
    return out


def spans_of(server: Server, trace_id: str) -> list[dict]:
    for t in server.get_json("/debug/traces")["traces"]:
        if t["trace_id"] == trace_id:
            return [{"name": s["name"], "ms": s["duration_ms"], "tags": s["tags"]}
                    for s in t["spans"] if s["name"] in SPANS]
    return []


def stages(spans: list[dict]) -> dict:
    """A request's stages on one line: the milliseconds of each TopN
    span, how the prep entry was come by (``prep_cache``), where it
    was built, whether any fragment was walked (``build``), whether a
    direct build was served from its view's kept stack or made it
    (``stack``: ``kept`` / ``made``), and whether the winners were
    selected from the entry's stacked arrays (``topn.select``'s
    ``way``: ``stacked``); a program from before a tag prints None for
    it."""
    by_name = {s["name"]: s for s in spans}
    out = {name.removeprefix("topn."): by_name[name]["ms"]
           for name in ("topn.prep", "topn.dispatch", "topn.fetch", "topn.select")
           if name in by_name}
    tags = by_name.get("topn.prep", {}).get("tags", {})
    way = by_name.get("topn.select", {}).get("tags", {}).get("way")
    return dict(out, prep_cache=tags.get("prep_cache"), build=tags.get("build"),
                stack=tags.get("stack"), way=way)


def probe(server: Server, cell, ref, srcs, deadline_ms: int) -> list[dict]:
    cfg, out = cell.config, []
    for src in srcs:
        text = (f"TopN(Bitmap(frame={cfg['frame']}, rowID={src}), "
                f"frame={cfg['frame']}, n={cfg['n']})")
        rec = ask(server, cfg["index"], text, deadline_ms)
        answer = rec.pop("answer", None)
        if answer is not None:
            rec["pairs"] = len(answer)
            rec["correct"] = (cell.kind.normalise(answer)
                              == ref.answer(("TopN", src, cfg["n"])))
        rec["spans"] = spans_of(server, rec["trace_id"])
        rec["stages"] = stages(rec["spans"])
        run.say(json.dumps(rec))
        out.append(rec)
    return out


def profiled(server: Server, cell, r, args) -> list[dict]:
    """Requests one at a time under ``GET /debug/profile``; the profile's
    ``.xplane.pb`` is kept under ``chiprun_out/`` for reading on the CPU."""
    def take() -> None:
        status, data = server.request(
            "GET", f"/debug/profile?seconds={args.profile_seconds:g}")
        if status == 200:
            r.profile_reply = json.loads(data)

    t = threading.Thread(target=take)
    t.start()
    time.sleep(1.0)
    out = probe(server, cell, r.ref, [50, 51, 52, 53], args.deadline_ms)
    t.join()
    pb = r.unpack_profile()
    if pb:
        os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
        shutil.copy(pb, os.path.join(ROOT, "chiprun_out", "topn_probe.xplane.pb"))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="segment-1b.topn-src")
    ap.add_argument("--seed", type=int, default=2_900_000_101)
    ap.add_argument("--srcs", default="3,17,40,0")
    ap.add_argument("--again", default="5,21", help="srcs after the restart")
    ap.add_argument("--deadline-ms", type=int, default=0)
    ap.add_argument("--concurrent", type=int, default=8)
    ap.add_argument("--profile-seconds", type=float, default=0.0,
                    help="also profile four requests and keep the .xplane.pb")
    ap.add_argument("--slices", type=int, default=0,
                    help="cut the configuration (a rehearsal on the CPU)")
    args = ap.parse_args(argv)

    bench = run.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = run.Cell(bench, args.workload)
    if args.slices:
        cell.config.update(slices=args.slices)
    r = run.Run(cell, args.seed, 0.0, True, run.Rig())
    doc: dict = {"workload": args.workload, "seed": args.seed}
    try:
        r.boot()
        doc["device"] = r.device
        r.load()
        doc["setup"] = dict(r.setup, prewarm_wait_s=wait_prewarm(r.server))
        doc["first_process"] = probe(r.server, cell, r.ref,
                                     [int(s) for s in args.srcs.split(",")],
                                     args.deadline_ms)
        doc["hbm_first"] = {k: r.server.get_json("/debug/hbm")[k]
                            for k in ("resident_bytes", "cache_bytes", "counters")}
        run.say(f"server stopped with {r.server.stop()}")

        # A new process on the same data: what the persistent cache saves.
        again = Server(r.server.data_dir, os.path.join(r.work, "server2.log"),
                       r.server.extra_env)
        r.server = again
        t0 = time.monotonic()
        again.start()
        again.wait_listening(timeout=600)
        doc["restart"] = {"boot_s": time.monotonic() - t0,
                          "prewarm_wait_s": wait_prewarm(again)}
        doc["second_process"] = probe(again, cell, r.ref,
                                      [int(s) for s in args.again.split(",")],
                                      args.deadline_ms)
        if args.profile_seconds:
            doc["profiled"] = profiled(again, cell, r, args)

        # As the cell's warm-up sends them: distinct texts at once.
        cfg = cell.config
        texts = [f"TopN(Bitmap(frame={cfg['frame']}, rowID={30 + i}), "
                 f"frame={cfg['frame']}, n={cfg['n']})"
                 for i in range(args.concurrent)]
        got: list = [None] * len(texts)

        def one(i: int) -> None:
            got[i] = ask(again, cfg["index"], texts[i], args.deadline_ms)

        threads = [threading.Thread(target=one, args=(i,)) for i in range(len(texts))]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for rec in got:
            rec.pop("answer", None)
            rec["spans"] = spans_of(again, rec["trace_id"])
            rec["stages"] = stages(rec["spans"])
            run.say(json.dumps({k: rec[k] for k in ("text", "wall_s", "stages")}))
        doc["concurrent"] = {"wall_s": time.monotonic() - t0, "requests": got}
        hbm = again.get_json("/debug/hbm")
        doc["hbm_second"] = {k: hbm[k] for k in ("resident_bytes", "cache_bytes",
                                                 "counters")}
        doc["compile_ms"] = {
            k: v for k, v in again.metrics().items() if "compileMs" in k}
        run.say(f"server stopped with {again.stop()}")
    except BaseException:
        run.say("the server's log ends:\n" + r.log_tail())
        raise
    finally:
        r.close()
    line = json.dumps(doc)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "topn_probe.json"), "w") as f:
        f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
