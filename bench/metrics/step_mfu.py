"""Model step: model FLOPs of every token prefilled or decoded in the
traced window over the window's seconds times the chip's bf16 peak, in %.
Prefill tokens count their projections, MLP and causal attention; decode
tokens also their attention over the live cache and the LM head."""
from bench import costs


def value(run):
    if run.trace is None:
        return None
    m, flops = run.dims, 0
    for s in run.window_steps():
        flops += sum(costs.prefill_flops(m, start, n) for start, n in s.chunks)
        flops += sum(costs.token_flops(m, ctx, lm_head=True)
                     for ctx in s.decode_lens)
    return 100.0 * flops / (run.trace.window_s * run.peak["bf16_flops"])
