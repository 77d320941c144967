"""Megabytes the prefetch buffer moved host to device in the window
(``PrefetchBuffer.stats.bytes_h2d``), per request round retrieved."""


def read(w):
    rounds = sum(len(r.queries) for r in w.retrievals)
    return w.bytes_h2d / 1e6 / rounds if rounds else None
