"""Process start to the first measured drain: datastore and index,
weights, server, compiles or cache reads, warm-up (host clock)."""


def read(w):
    return w.setup_s
