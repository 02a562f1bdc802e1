"""Unique states of every check in the window over the window's wall time."""


def read(run):
    if not run.checks or run.window_s <= 0:
        return None
    return sum(c.unique for c in run.checks) / run.window_s
