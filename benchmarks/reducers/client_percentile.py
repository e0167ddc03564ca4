"""A percentile of the client's own latencies (the generator's clock)
over every answered request of a ``kind``.  In a closed loop the median
only restates the rate; the tail shows what stalled."""

from metrics import percentile


def read(ev, q, kind="read"):
    xs = [r["latency_ms"] for r in ev["records"] if r["kind"] == kind and r["ok"]]
    return percentile(xs, q) if xs else None
