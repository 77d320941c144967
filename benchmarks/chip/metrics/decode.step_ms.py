"""Decode seconds over decode steps in the window, from
``DecodeRunner.wave_step_seconds`` (host clock per wave, ending in
``block_until_ready``) weighted by each wave's steps."""


def read(w):
    steps = [v.steps for v in w.waves if v.steps]
    if not steps or len(steps) != len(w.wave_seconds):
        return None
    return 1e3 * sum(s * n for s, n in zip(w.wave_seconds, steps)) / sum(steps)
