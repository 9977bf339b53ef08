"""Time of the harness ``interval`` spans that no program span covers, per
completed window interval, in ms: the entry call's own work between the
program's phases. With ``plan`` and the other ``_ms`` spans it adds up to
the intervals' time."""

import spanreduce


def read(run):
    return spanreduce.span_ms(run, (spanreduce.UNTRACED,))
