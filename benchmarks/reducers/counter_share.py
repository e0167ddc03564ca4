"""Percent that the growth over the window of the ``/metrics`` series
in ``num`` (a list, summed) is of the growth of those in ``den`` — or,
with no ``den``, of the window's own length in ms, for series that count
milliseconds of one thread's life: the dispatcher's launch time as a
share of the window.  (Not of the growth of its idle + launch + host
time: the closing scrape of a traced run comes after ``/debug/profile``
has returned, minutes after the load stopped in some cells, and all of
that wait is idle time.)"""


def read(ev, num, den=None):
    before, after = ev["metrics"]["before"], ev["metrics"]["after"]
    if any(series not in after for series in (*num, *(den or ()))):
        return None

    def growth(names):
        return sum(after[s] - before.get(s, 0.0) for s in names)

    w0, w1 = ev["window"]
    total = growth(den) if den else (w1 - w0) * 1e3
    return 100.0 * growth(num) / total if total > 0 else None
