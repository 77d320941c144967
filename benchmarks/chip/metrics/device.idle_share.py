"""1 - (union of device-op intervals / traced window), from the
profiler trace of a ``--trace 1`` run."""

from benchmarks.chip import xplane


def read(w):
    if w.trace is None:
        return None
    lo, hi = w.trace_window
    return 1.0 - xplane.busy_ns(w.device_ops(), w.trace_window) / (hi - lo)
