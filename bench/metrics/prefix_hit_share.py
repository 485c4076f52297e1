"""Scheduler: share of the prompt tokens admitted inside the measured
window that the prefix cache served (their prefill skipped), in %."""


def value(run):
    adm = [(n, hit) for t, n, hit in run.admissions if run.t0 <= t <= run.t1]
    total = sum(n for n, _ in adm)
    return 100.0 * sum(h for _, h in adm) / total if total else None
