"""Median over requests of the client's latency less the request's own
span ``span``: the time spent outside that span (socket, HTTP framing,
parse, tenant, admission, serialising the reply)."""

from metrics import percentile


def read(ev, span):
    by_id = {t["trace_id"]: t for t in ev["traces"]}
    out = []
    for r in ev["records"]:
        t = by_id.get(r["trace_id"])
        if t is None or not r["ok"]:
            continue
        inside = [s["duration_ms"] for s in t["spans"]
                  if s["name"] == span and s["duration_ms"] is not None]
        if inside:
            out.append(r["latency_ms"] - sum(inside))
    return percentile(out, 50) if out else None
