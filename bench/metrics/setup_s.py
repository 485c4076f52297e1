"""Set-up time: process start to the measured window's start, with the
weights, the engine, the warm-up and the traffic's ramp in it."""


def value(run):
    return run.setup_s
