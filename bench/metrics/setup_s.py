"""Seconds from process start to the first timed interval: start-up,
traffic, warm-up intervals and every compile or cache load."""


def read(run):
    return run.setup_s
