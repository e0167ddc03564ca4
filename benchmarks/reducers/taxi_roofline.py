"""Share of the HBM roofline at which the device scored the
``TopN(src)`` answers of a ``taxi-rides`` window.

Bytes asked for: per scored answer, the ranked frame's rows (as the
configuration states them) plus the src row, x the slices that the
text's src reaches under the configuration's data rule
(``deployments/taxi-rides.py``: a year's rides are one run of columns
in pickup-time order, and every passenger count is held in every slice
of every year), x one dense (slice, row) plane.  Both come from the
configuration and the request's text, never from the program's tags or
padded shapes: a program that scores every slice, or every row of a
padded class, reads lower for it, and none can read over 100 % unless
it skips work the text asks for.

Counted and divided as ``topn_roofline.py`` does: an answer counts when
its ``topn.score`` says ``computed`` (a score shared from the memo
streamed nothing) and its ``topn.dispatch`` opened and its
``topn.fetch`` closed inside the profile; the least time for its bytes
is bytes over chips x the chip's HBM peak; it is divided by all the
time in which a program ran on a device (the mean over the devices
used, ``topn_roofline.program_s``), so padding, copies, any other
program and the work of answers that straddle the profile's ends count
against it.
"""

import re

import numpy as np

from metrics import peak
from reducers.topn_roofline import program_s

LEAF = re.compile(r"Bitmap\(frame=(\w+), rowID=(\d+)\)")
RANKED = re.compile(r"frame=(\w+), n=\d+\)\s*$")


def reached_slices(cfg: dict, text: str) -> int:
    """The slices the text's src reaches: a year leaf's, else all."""
    years = [int(row) for frame, row in LEAF.findall(text)
             if frame == cfg["year"]["frame"]]
    if not years:
        return int(cfg["slices"])
    starts = np.concatenate([[0], np.cumsum(cfg["year"]["rides"])]).astype(np.int64)
    y, w = years[0] - int(cfg["year"]["first"]), int(cfg["slice_width"])
    return int((starts[y + 1] - 1) // w - starts[y] // w + 1)


def read(ev):
    prof = ev.get("profile")
    if not prof or ev["device"]["platform"] != "tpu" or not prof["devices"]:
        return None
    cfg = ev["config"]
    rows = {cfg[dim]["frame"]: int(cfg[dim]["rows"]) for dim in ("passengers", "distance")}
    texts = {r["trace_id"]: r["text"] for r in ev["records"] if r["trace_id"]}
    need = scored = 0
    for t in ev["traces"]:
        text = texts.get(t["trace_id"])
        spans = {s["name"]: s for s in t["spans"]}
        score, disp, fetch = (spans.get(n) for n in
                              ("topn.score", "topn.dispatch", "topn.fetch"))
        if (text is None or not (score and disp and fetch)
                or fetch["duration_ms"] is None):
            continue
        ranked = RANKED.search(text)
        done = fetch["start"] + fetch["duration_ms"] / 1e3
        if (ranked and ranked.group(1) in rows
                and score["tags"].get("score_cache") == "computed"
                and prof["start"] <= disp["start"] and done <= prof["stop"]):
            need += (rows[ranked.group(1)] + 1) * reached_slices(cfg, text)
            scored += 1
    busy = sum(program_s(ops) for ops in prof["devices"].values()) / len(prof["devices"])
    if scored == 0 or busy <= 0:
        return None
    need *= int(cfg["slice_width"]) // 8
    chips = ev["device"]["count"]
    least = need / (chips * peak(ev["device"]["kind"])["hbm_bytes_per_s"])
    return 100.0 * least / busy
