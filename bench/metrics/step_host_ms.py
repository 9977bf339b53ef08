"""Self time of the program span ``step`` per completed window interval, in
ms: the host part of the fused step: the ``np.bincount`` histogram, the
uploads and the dispatch."""

import spanreduce

SPANS = ("step",)


def read(run):
    return spanreduce.span_ms(run, SPANS)
