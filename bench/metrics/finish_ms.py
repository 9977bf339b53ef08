"""Self time of the program span ``finish`` per completed window interval,
in ms: the closed forms, outputs, emitted sum, task cost and host mirrors
after the step."""

import spanreduce

SPANS = ("finish",)


def read(run):
    return spanreduce.span_ms(run, SPANS)
