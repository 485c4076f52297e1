"""Model step: device time of the held-expert grouped matmuls (megablox
``gmm`` ops) per decode launch in the traced window, in ms.  None where
the decode launches run no such op."""
OPS = ("gmm",)                      # the grouped matmul's op name
LAUNCH = "jit_decode_step"


def value(run):
    t = run.trace
    if t is None:
        return None
    n = len(t.launches(LAUNCH))
    gmm = sum(op[4] for op in t.ops if op[0] in OPS and op[1] == LAUNCH)
    return 1e3 * gmm / n if n and gmm else None
