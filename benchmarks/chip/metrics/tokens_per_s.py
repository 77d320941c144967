"""Tokens that live rows decoded for the window's requests, over the
window's span (host clock).  Padding rows and steps past a row's own
length are not counted."""


def read(w):
    return sum(sum(v.gens) for v in w.waves) / w.span_s
