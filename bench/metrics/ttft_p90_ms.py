"""90th percentile of time to first token, from each request's due time,
over every request due inside the measured window.  A request with no
first token by the window's end enters at its wait so far."""
import numpy as np


def value(run):
    waits = [(min(r.first, run.t1) if r.first is not None else run.t1) - r.due
             for r in run.recs if run.t0 <= r.due < run.t1]
    if not waits:
        return None
    return float(np.percentile(waits, 90)) * 1e3
