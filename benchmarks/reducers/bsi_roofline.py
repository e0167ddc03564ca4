"""Share of the HBM roofline at which the device computed the filtered
BSI ``Sum`` answers of the traced window.

Bytes asked for: per answer, the rows of dense planes it has to read in
every slice (:func:`planes_of`: from the request's text and the
configuration's field ranges, never from the program's padded shapes)
x the configuration's real slices x one dense (slice, row) plane.  An
answer is counted when its ``bsi.agg`` span says ``in_place`` (the
leaf-batch way copies its rows first and is another program) and its
``bsi.dispatch`` opened and its ``bsi.fetch`` closed inside the
profile, so that all of its device work lies in the time that is
divided by.  The least time for those bytes is bytes over chips x the
chip's HBM peak; it is divided by all the time in which a program ran
on a device (the mean over the devices used; ``topn_roofline.program_s``:
holes under 50 us inside a launch are closed), so padding, any other
program and the work of answers that straddle the profile's ends count
against it: it reads low, never over 100 %.
"""

import re

from metrics import peak
from reducers.topn_roofline import program_s

RANGE_FIELD = re.compile(r"Range\(frame=\w+, (\w+) ")
SUM_FIELD = re.compile(r"field=(\w+)\)$")


def field_planes(bounds) -> int:
    """Rows a BSI field stores: the not-null row, a magnitude row a bit
    of the largest magnitude, and the sign row only where a value can be
    negative (no column sets it otherwise, and a row no column ever set
    is not stored)."""
    lo, hi = bounds
    return 1 + max(1, max(abs(lo), abs(hi)).bit_length()) + (lo < 0)


def planes_of(text: str, config: dict) -> int:
    """Rows of planes one answer reads in every slice: the summed
    field's, each ranged field's, and one a ``Bitmap``."""
    fields = config["measures"]["fields"]
    named = SUM_FIELD.findall(text) + RANGE_FIELD.findall(text)
    return sum(field_planes(fields[f]) for f in named) + text.count("Bitmap(")


def read(ev):
    prof = ev.get("profile")
    if not prof or ev["device"]["platform"] != "tpu" or not prof["devices"]:
        return None
    text_of = {r["trace_id"]: r["text"] for r in ev["records"]}
    planes = 0
    for t in ev["traces"]:
        spans = {s["name"]: s for s in t["spans"]}
        agg, disp, fetch = (spans.get(n) for n in ("bsi.agg", "bsi.dispatch", "bsi.fetch"))
        if not (agg and disp and fetch) or fetch["duration_ms"] is None:
            continue
        done = fetch["start"] + fetch["duration_ms"] / 1e3
        if (agg["tags"].get("way") == "in_place" and t["trace_id"] in text_of
                and prof["start"] <= disp["start"] and done <= prof["stop"]):
            planes += planes_of(text_of[t["trace_id"]], ev["config"])
    busy = sum(program_s(ops) for ops in prof["devices"].values()) / len(prof["devices"])
    if planes == 0 or busy <= 0:
        return None
    cfg = ev["config"]
    need = planes * cfg["slices"] * (cfg["slice_width"] // 8)
    chips = ev["device"]["count"]
    least = need / (chips * peak(ev["device"]["kind"])["hbm_bytes_per_s"])
    return 100.0 * least / busy
