"""Share of the traced window in which no operation ran on the chip, %."""


def read(run):
    if run.summary is None:
        return None
    return 100.0 * run.summary.idle_share
