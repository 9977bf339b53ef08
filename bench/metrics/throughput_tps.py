"""Tuples of the intervals completed in the window, over the window's
seconds (the window closes at the first completion past its length)."""


def read(run):
    done = run.done()
    if not done or run.window_s <= 0:
        return None
    return sum(iv.tuples for iv in done) / run.window_s
