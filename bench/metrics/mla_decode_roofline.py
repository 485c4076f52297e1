"""Kernels: least time the latent (MLA) paged decode kernel needs for the
live cache (each decoding slot's rows over every layer) over its time in
the trace, in %.  The least time per decode launch, averaged over the
host's decode steps of the traced window, is scaled to the number of
decode launches the trace holds.  None where the trace has no such
kernel."""
from bench import costs, mla_costs

KERNEL = "mla_decode_attention"     # the kernel's op name on the trace
LAUNCH = "jit_decode_step"          # the decode executable's module


def value(run):
    t = run.trace
    if t is None or "rank" not in run.dims:
        return None
    m = run.dims
    kernel = sum(op[4] for op in t.ops if op[0] == KERNEL)
    steps = [s for s in run.window_steps() if s.decode_lens]
    n = len(t.launches(LAUNCH))
    if not kernel or not steps or not n:
        return None
    least = sum(m["layers"] * costs.roofline_seconds(
        *mla_costs.mla_decode_call(s.decode_lens, heads=m["h"],
                                   rank=m["rank"], rope=m["rope"]),
        run.peak) for s in steps)
    return 100.0 * least / len(steps) * n / kernel
