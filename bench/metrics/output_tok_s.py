"""Output tokens stamped inside the measured window over its seconds."""


def value(run):
    n = sum(1 for r in run.recs for t in r.stamps if run.t0 <= t <= run.t1)
    return n / run.seconds
