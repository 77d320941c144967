"""Median request latency: from the start of the request's drain to the
end of the last call that worked for it (host clock)."""

import statistics


def read(w):
    return statistics.median(w.latencies) if w.latencies else None
