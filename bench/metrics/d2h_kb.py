"""Device-to-host bytes per completed window interval, in kB (1 kB =
1000 B): the change of the program's ``d2h_bytes`` counter over the window,
which every copy through ``repro.streams.device.to_host`` adds to."""

import spanreduce


def read(run):
    return spanreduce.counter_kb(run, "d2h_bytes")
