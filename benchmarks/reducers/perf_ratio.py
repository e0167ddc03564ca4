"""Ratio of two ``/debug/perf`` counts over the window, summed over
``sites`` (the sites a Count can ride on, unless said): for instance
queries per launch, the coalescer's occupancy."""

from metrics import COUNT_SITES


def read(ev, num, den, sites=COUNT_SITES):
    def delta(key):
        total = 0
        for site in sites:
            after = ev["perf"]["after"].get(site, {}).get(key, 0)
            before = ev["perf"]["before"].get(site, {}).get(key, 0)
            total += after - before
        return total

    d = delta(den)
    return delta(num) / d if d > 0 else None
