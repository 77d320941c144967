"""``flash_decode_paged``'s share of its roofline (%): the least time
its calls in the traced part of the window require (the larger of FLOPs over peak
FLOP/s and bytes over peak bandwidth, ``counts.flash_decode_call``)
over the kernel's summed device time in the trace.

One call per layer per decode step, over the micro-batch: at step t a
live row attends over t + 1 tokens, a padding row over 1."""

import sys

from benchmarks.chip import counts, xplane

NAMES = ("flash_decode_paged",)


def read(w):
    if w.trace is None or w.peak is None:
        return None
    ns, calls = xplane.kernel_ns(w.device_ops(), w.trace_window, NAMES)
    if not calls:
        return None
    flops = nbytes = 0.0
    want = 0
    for v in w.traced_waves:
        for t in range(v.steps):
            lengths = [t + 1] * v.live + [1] * (v.rows - v.live)
            f, b = counts.flash_decode_call(w.shape, lengths, w.kv_page_size)
            flops += f * w.shape.layers
            nbytes += b * w.shape.layers
        want += v.steps * w.shape.layers
    share, bound = counts.roofline(flops, nbytes, ns * 1e-9, w.peak)
    print(f"# flash_decode_paged: {calls} calls in the trace, {want} "
          f"counted; {flops:.6g} FLOPs, {nbytes:.6g} bytes, "
          f"{ns * 1e-9:.6f} s; {bound} bound", file=sys.stderr)
    return share
