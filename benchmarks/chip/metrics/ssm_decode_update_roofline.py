"""``ssm_decode_update``'s share of its roofline (%): the least time its
calls in the traced part of the window require (the larger of FLOPs over
peak FLOP/s and bytes over peak bandwidth, ``ssm_counts.ssm_decode_call``)
over the kernel's summed device time in the trace.

One call per Mamba-2 layer per decode step, over every row the step
runs (padding rows update a scratch slot at the same cost).  A shape
with no Mamba-2 layers, or a trace with no such call, reads nothing."""

import sys

from benchmarks.chip import counts, ssm_counts, xplane

NAMES = ("ssm_decode_update",)


def read(w):
    layers = getattr(w.shape, "mamba_layers", 0)
    if w.trace is None or w.peak is None or not layers:
        return None
    ns, calls = xplane.kernel_ns(w.device_ops(), w.trace_window, NAMES)
    if not calls:
        return None
    flops = nbytes = 0.0
    want = 0
    for v in w.traced_waves:
        f, b = ssm_counts.ssm_decode_call(v.rows, w.shape.ssm_heads,
                                          w.shape.ssm_head_dim,
                                          w.shape.ssm_state)
        flops += f * v.steps * layers
        nbytes += b * v.steps * layers
        want += v.steps * layers
    share, bound = counts.roofline(flops, nbytes, ns * 1e-9, w.peak)
    print(f"# ssm_decode_update: {calls} calls in the trace, {want} "
          f"counted; {flops:.6g} FLOPs, {nbytes:.6g} bytes, "
          f"{ns * 1e-9:.6f} s; {bound} bound", file=sys.stderr)
    return share
