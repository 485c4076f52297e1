"""Model step: device time per chunked-prefill launch (the launches that
run the paged chunk-prefill kernel) in the traced window, mean."""


def value(run):
    if run.trace is None:
        return None
    d = run.trace.launches("prefill")
    return 1e3 * sum(d) / len(d) if d else None
