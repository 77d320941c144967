"""``ssm_decode_update``'s summed device time over the device's busy
time in the traced window (the union of device-op intervals), from the
profiler trace of a ``--trace 1`` run.  A trace with no such call reads
nothing."""

from benchmarks.chip import xplane

NAMES = ("ssm_decode_update",)


def read(w):
    if w.trace is None:
        return None
    ops = w.device_ops()
    ns, calls = xplane.kernel_ns(ops, w.trace_window, NAMES)
    if not calls:
        return None
    return ns / xplane.busy_ns(ops, w.trace_window)
