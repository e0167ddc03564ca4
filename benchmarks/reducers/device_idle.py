"""Percent of the traced window in which no operation ran, on the
device that was idle most.  Nothing to read off a chip or with no
trace: a CPU run never reports it."""

from xplane import busy_s


def read(ev):
    prof = ev.get("profile")
    if not prof or ev["device"]["platform"] != "tpu" or not prof["devices"]:
        return None
    span = prof["stop"] - prof["start"]
    return 100.0 * max(1.0 - busy_s(ops) / span for ops in prof["devices"].values())
