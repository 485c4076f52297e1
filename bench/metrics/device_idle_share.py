"""Device: share of the traced window in which no op ran on the chip, %."""


def value(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
