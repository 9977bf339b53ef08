"""Controller plan time per window interval, in ms: every stage's
``plan_time_s`` of the plans made in the window, over its intervals."""


def read(run):
    n = len(run.done())
    return 1e3 * run.plan_s / n if n else None
