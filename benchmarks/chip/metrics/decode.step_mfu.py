"""Share of the chip's bf16 peak (%) that the window's live decoded
tokens require over the decode steps' seconds: model FLOPs from the
configuration's shapes (``counts.wave_flops``), not what was run."""

from benchmarks.chip import counts


def read(w):
    live = [v for v in w.waves if v.steps]
    if w.peak is None or not live or len(live) != len(w.wave_seconds):
        return None
    flops = sum(counts.wave_flops(w.shape, v.gens) for v in live)
    secs = sum(s * v.steps for s, v in zip(w.wave_seconds, live))
    return 100.0 * flops / (secs * w.peak.bf16_flops)
