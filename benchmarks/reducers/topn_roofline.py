"""Share of the HBM roofline at which the device scored the TopN(src)
answers of the traced window.

Bytes asked for: per scored answer, the configuration's rows (every
row is a candidate in every slice, as its file states) plus the src row,
x the configuration's real slices x one dense (slice, row) plane; taken
from the configuration's file, never from the program's padded shapes.
An answer is counted when its ``topn.score`` span says ``computed`` (a
score shared from the memo streamed nothing) and its ``topn.dispatch``
opened and its ``topn.fetch`` closed inside the profile, so that all of
its device work lies in the time that is divided by; a plain TopN has
neither span and asks for no byte.  The least time for those bytes is
bytes over chips x the chip's HBM peak; it is divided by all the time
in which a program ran on a device (the mean over the devices used), so
padding, copies, any other program and the work of answers that
straddle the profile's ends count against it.

**The time a program ran.**  The scorer streams its planes by
asynchronous copies, and between two operations of one launch the op
line of the profile is empty for a microsecond or less (99 in 100 of
the holes are under 1.8 us) while the copies stay in flight.  The plain
union of the operations' intervals (``xplane.busy_s``) leaves that time
out: over 60 launches it read 41.3 ms where the profile's own line of
programs read 47.3 ms, and 8.8 ms an answer where the bytes need 9.9 ms
at the peak, a share of 113 % (my chip runs, PR 29).  So holes shorter
than ``HOLE_S`` are closed before the intervals are summed, which gives
the line of programs' 47.5 ms.  Two launches are a millisecond or more
apart (the host dispatches them), so no idle time between programs is
taken for work.
"""

from metrics import peak
from xplane import union

# The longest hole inside a running program that is taken for part of
# it: far above the gaps between a launch's operations, far below
# the gap between two launches.
HOLE_S = 50e-6


def program_s(ops: list) -> float:
    """Seconds in which a program ran: the union of the operations'
    intervals with the holes under ``HOLE_S`` closed."""
    total, end = 0.0, None
    for a, b in union(ops):
        if end is not None and a - end < HOLE_S:
            total += a - end
        total += b - a
        end = b
    return total


def read(ev):
    prof = ev.get("profile")
    if not prof or ev["device"]["platform"] != "tpu" or not prof["devices"]:
        return None
    scored = 0
    for t in ev["traces"]:
        spans = {s["name"]: s for s in t["spans"]}
        score, disp, fetch = (spans.get(n) for n in
                              ("topn.score", "topn.dispatch", "topn.fetch"))
        if not (score and disp and fetch) or fetch["duration_ms"] is None:
            continue
        done = fetch["start"] + fetch["duration_ms"] / 1e3
        if (score["tags"].get("score_cache") == "computed"
                and prof["start"] <= disp["start"] and done <= prof["stop"]):
            scored += 1
    busy = sum(program_s(ops) for ops in prof["devices"].values()) / len(prof["devices"])
    if scored == 0 or busy <= 0:
        return None
    cfg = ev["config"]
    plane = cfg["slice_width"] // 8
    need = scored * (cfg["rows"] + 1) * cfg["slices"] * plane
    chips = ev["device"]["count"]
    least = need / (chips * peak(ev["device"]["kind"])["hbm_bytes_per_s"])
    return 100.0 * least / busy
