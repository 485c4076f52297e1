"""Model step: device time per decode-step launch (the launches that run
the paged decode kernel) in the traced window, mean."""


def value(run):
    if run.trace is None:
        return None
    d = run.trace.launches("decode")
    return 1e3 * sum(d) / len(d) if d else None
