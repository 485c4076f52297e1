"""Scheduler: live slots per decode launch (slots that emitted a token),
averaged over the decode launches of the traced window."""


def value(run):
    b = [len(s.decode_lens) for s in run.window_steps() if s.decode_lens]
    return sum(b) / len(b) if b else None
