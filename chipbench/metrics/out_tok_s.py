"""Generated tokens of every call in the window over the window's time."""


def read(run):
    return sum(c.batch * c.n_new for c in run.calls) / run.window_s
