"""Share of the HBM roofline at which the device scored the
``TopN(src, tanimotoThreshold)`` answers of the traced window.

Bytes asked for: per scored answer, the rows whose cardinality lies in
that text's count window plus the src row, x ``fingerprint_bits`` / 8:
the work the query's semantics ask, the same whatever implements it,
and never a padded shape, a block size or the table's whole length.  A
reducer's ``ev`` holds neither the seed nor the reference, so the
window's row count is read off the answer's ``topn.prep`` span, tag
``candidates``: a number of the semantics (upstream's filter on cached
counts), which the kind's reference computes too (``window_rows``) and
a tier-1 test holds the tag to.  A program that streams every row and
masks still reports the window's count, and reads lower for it; none
can read over 100 %.

Counted and divided as ``topn_roofline.py`` does: an answer counts when
its ``topn.score`` says ``computed`` (a score shared from the memo
streamed nothing) and its ``topn.dispatch`` opened and its
``topn.fetch`` closed inside the profile; the least time for its bytes
is bytes over chips x the chip's HBM peak; it is divided by all the
time in which a program ran on a device (the mean over the devices
used, ``topn_roofline.program_s``: the holes inside a running program
closed), so
padding, copies, any other program and the work of answers that
straddle the profile's ends count against it.  A program without the
``candidates`` tag (the parent) gives nothing to read.
"""

from metrics import peak
from reducers.topn_roofline import program_s


def read(ev):
    prof = ev.get("profile")
    if not prof or ev["device"]["platform"] != "tpu" or not prof["devices"]:
        return None
    rows = scored = 0
    for t in ev["traces"]:
        spans = {s["name"]: s for s in t["spans"]}
        prep, score, disp, fetch = (
            spans.get(n) for n in ("topn.prep", "topn.score", "topn.dispatch", "topn.fetch"))
        if not (prep and score and disp and fetch) or fetch["duration_ms"] is None:
            continue
        candidates = prep["tags"].get("candidates")
        done = fetch["start"] + fetch["duration_ms"] / 1e3
        if (candidates is not None
                and score["tags"].get("score_cache") == "computed"
                and prof["start"] <= disp["start"] and done <= prof["stop"]):
            rows += int(candidates) + 1
            scored += 1
    busy = sum(program_s(ops) for ops in prof["devices"].values()) / len(prof["devices"])
    if scored == 0 or busy <= 0:
        return None
    need = rows * ev["config"]["fingerprint_bits"] // 8
    chips = ev["device"]["count"]
    least = need / (chips * peak(ev["device"]["kind"])["hbm_bytes_per_s"])
    return 100.0 * least / busy
