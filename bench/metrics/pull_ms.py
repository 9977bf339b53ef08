"""Self time of the program span ``pull`` per completed window interval, in
ms: the device-to-host copies (``repro.streams.device.to_host``)."""

import spanreduce

SPANS = ("pull",)


def read(run):
    return spanreduce.span_ms(run, SPANS)
