"""Median duration of the spans named ``span``; with ``self_time`` the
part of each that its child spans do not cover."""

from metrics import children_ms, percentile


def read(ev, span, self_time=False):
    out = []
    for t in ev["traces"]:
        for s in t["spans"]:
            if s["name"] != span or s["duration_ms"] is None:
                continue
            ms = s["duration_ms"]
            if self_time:
                ms -= children_ms(t, s)
            out.append(ms)
    return percentile(out, 50) if out else None
