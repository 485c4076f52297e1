"""Prompt tokens prefilled by the steps that ended inside the measured
window (each step's chunks, as the scheduler planned them), over the
window's seconds.  Prompt tokens the prefix cache served are not
prefilled and do not count."""


def value(run):
    n = sum(c for s in run.steps_in(run.t0, run.t1) for _, c in s.chunks)
    return n / run.seconds
