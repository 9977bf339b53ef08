"""Self time of the program span ``route`` per completed window interval, in
ms: a dense route refresh on a cache miss: the table arrays, the
``routing_lookup`` dispatch and its uploads, the start of its host copy."""

import spanreduce

SPANS = ("route",)


def read(run):
    return spanreduce.span_ms(run, SPANS)
