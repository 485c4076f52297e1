"""Kernels: least time the paged decode attention needs for the live
cache (each decoding slot's entries, not the ``max_seq`` pages its grid
walks) over its time in the trace, in %.  The least time per decode
launch, averaged over the host's decode steps of the traced window, is
scaled to the number of decode launches the trace holds."""
from bench import costs


def value(run):
    t = run.trace
    if t is None:
        return None
    m = run.dims
    kernel = t.kernel_s("paged_decode")
    steps = [s for s in run.window_steps() if s.decode_lens]
    n = len(t.launches("decode"))
    if not kernel or not steps or not n:
        return None
    least = sum(m["layers"] * costs.roofline_seconds(*costs.paged_decode_call(
        s.decode_lens, kv_heads=m["kv"], group=m["h"] // m["kv"], dh=m["dh"]),
        run.peak) for s in steps)
    return 100.0 * least / len(steps) * n / kernel
