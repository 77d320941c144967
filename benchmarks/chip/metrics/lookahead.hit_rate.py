"""Probed clusters found resident over all probed clusters, summed over
the window's rounds (``RoundTelemetry.hits`` / ``misses``)."""


def read(w):
    hits = misses = 0
    for d in w.drains:
        for r in d.responses:
            hits += sum(rt.hits for rt in r.rounds)
            misses += sum(rt.misses for rt in r.rounds)
    return hits / (hits + misses) if hits + misses else None
