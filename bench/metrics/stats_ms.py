"""Self time of the program spans ``stats`` and ``trigger`` per completed
window interval, in ms: the per-key stats build and the controller's
imbalance test."""

import spanreduce

SPANS = ("stats", "trigger")


def read(run):
    return spanreduce.span_ms(run, SPANS)
