"""Median host time of one interval's entry call over the window, in ms
(the call returns host arrays, so it ends when the interval's work has)."""

import numpy as np


def read(run):
    ms = [1e3 * (iv.done - iv.handed) for iv in run.done()]
    return float(np.median(ms)) if ms else None
