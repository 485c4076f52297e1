"""99th percentile of the gaps between successive output tokens of a
request, over every gap whose two tokens both fall inside the measured
window, of every request."""
import numpy as np


def value(run):
    gaps = []
    for r in run.recs:
        s = [t for t in r.stamps if run.t0 <= t <= run.t1]
        gaps.extend(b - a for a, b in zip(s, s[1:]))
    if not gaps:
        return None
    return float(np.percentile(gaps, 99)) * 1e3
