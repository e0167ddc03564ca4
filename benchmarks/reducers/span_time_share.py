"""Percent of the wall time of the spans named in ``spans`` that their
thread spent one way, ``part``: ``run`` (on the processor: ``cpu_ms``),
``blocked`` (waiting on purpose: ``blocked_ms``, a future of the
coalescer, its own mappers, a device fetch, a contended lock) or
``gil_wait`` (what is left: it wanted to run and did not — the GIL, the
OS run queue, a blocking call nobody wrapped).  Sums over the spans that
have ``cpu_ms`` and ``blocked_ms``, a span under another listed one not
counted twice; the three parts come to 100, and a remainder below zero
(``cpu_ms`` ticks) stays in the sums.

A wait of kind ``queue`` ends when the waiter runs again, so it holds
the ``handoff.wake`` spans beneath it: with the result ready, that time
was a wait for the GIL, and is moved from ``blocked`` to ``gil_wait``
(no more of it than the span was blocked for, kind ``queue``)."""


def _ancestors(span, by_id):
    seen = 0
    while span is not None and seen < 64:
        span = by_id.get(span.get("parent_id"))
        if span is not None:
            yield span
        seen += 1


def read(ev, spans, part):
    names = set(spans)
    dur = cpu = blocked = wake = 0.0
    for t in ev["traces"]:
        by_id = {s["span_id"]: s for s in t["spans"]}
        wakes = [s for s in t["spans"] if s["name"] == "handoff.wake"]
        for s in t["spans"]:
            if (s["name"] not in names or not s["duration_ms"]
                    or s.get("cpu_ms") is None or s.get("blocked_ms") is None
                    or any(a["name"] in names for a in _ancestors(s, by_id))):
                continue
            dur += s["duration_ms"]
            cpu += s["cpu_ms"]
            blocked += s["blocked_ms"]
            beneath = sum(
                w["duration_ms"] or 0.0 for w in wakes
                if any(a is s for a in _ancestors(w, by_id)))
            wake += min(beneath, s["tags"].get("blocked", {}).get("queue", 0.0))
    if not dur:
        return None
    return 100.0 * {
        "run": cpu,
        "blocked": blocked - wake,
        "gil_wait": dur - blocked - cpu + wake,
    }[part] / dur
