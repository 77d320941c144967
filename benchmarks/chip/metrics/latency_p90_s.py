"""90th percentile of the same latencies as ``latency_p50_s``."""

import statistics


def read(w):
    if len(w.latencies) < 2:
        return None
    return statistics.quantiles(w.latencies, n=10, method="inclusive")[8]
