"""Host-to-device bytes per completed window interval, in kB (1 kB =
1000 B), as placed on the devices: the change of the program's
``h2d_bytes`` counter over the window, which every upload through
``repro.streams.device.to_device`` adds to."""

import spanreduce


def read(run):
    return spanreduce.counter_kb(run, "h2d_bytes")
