"""Engine: median host time per ``engine.step()`` that no device op
covers (the harness's ``bench.step`` span minus the device's busy
intervals inside it), over the steps of the traced window."""
import numpy as np


def value(run):
    if run.trace is None:
        return None
    t = run.trace
    own = [t.uncovered(s, d) for s, d in t.host_spans("bench.step")
           if t.host_t0 <= s and s + d <= t.host_t1]
    return float(np.median(own)) * 1e3 if own else None
