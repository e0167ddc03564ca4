"""Share of the HBM roofline at which the device answered the Counts of
the traced window.

Bytes asked for: per answer, leaves x the configuration's real slices x
one dense (slice, row) plane, taken from the query and the
configuration's file, never from the program's padded shapes.  An
answer is in the traced window when its reply came inside it (the
device work ends a request).  The least time for those bytes is bytes
over chips x the chip's HBM peak; it is divided by all the time in
which any operation ran on a device (the mean over the devices used),
so padding, copies and any other program count against it.
"""

from metrics import peak
from xplane import busy_s


def read(ev, leaves=2):
    prof = ev.get("profile")
    if not prof or ev["device"]["platform"] != "tpu" or not prof["devices"]:
        return None
    answers = sum(
        1 for r in ev["records"]
        if r["kind"] == "read" and r["ok"] and prof["start"] <= r["done"] <= prof["stop"]
    )
    busy = sum(busy_s(ops) for ops in prof["devices"].values()) / len(prof["devices"])
    if answers == 0 or busy <= 0:
        return None
    cfg = ev["config"]
    plane = cfg["slice_width"] // 8
    need = answers * leaves * cfg["slices"] * plane
    chips = ev["device"]["count"]
    least = need / (chips * peak(ev["device"]["kind"])["hbm_bytes_per_s"])
    return 100.0 * least / busy
