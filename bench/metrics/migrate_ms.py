"""Self time of the program spans ``migrate`` and ``pause`` per completed
window interval, in ms: the relabel of the keys a plan moved and the pause
window's buffered count after it."""

import spanreduce

SPANS = ("migrate", "pause")


def read(run):
    return spanreduce.span_ms(run, SPANS)
