"""Live tokens decoded over (rows the step ran x steps), over the
window's decode waves: what padding rows and rows past their own length
waste."""


def read(w):
    done = sum(v.rows * v.steps for v in w.waves)
    return sum(sum(v.gens) for v in w.waves) / done if done else None
