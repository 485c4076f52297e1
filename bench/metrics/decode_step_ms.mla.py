"""Model step: device time per decode launch in the traced window, mean,
where the decode launches run the latent (MLA) kernel: the trace names
them by their module, ``jit_decode_step``."""
LAUNCH = "jit_decode_step"


def value(run):
    if run.trace is None:
        return None
    d = run.trace.launches(LAUNCH)
    return 1e3 * sum(d) / len(d) if d else None
