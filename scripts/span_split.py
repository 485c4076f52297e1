"""Split one benchmark run's device idle time by the engine's own spans.

    python scripts/span_split.py --workload qwen2-7b.chat --seed 7 \\
        --seconds 51 --trace 1

Runs one cell as ``bench/run.py`` does (on the chip, the same set-up,
window, trace and result line), with the engine's layers reporting to
``Observer(span=profiler_span)``, and adds one key to the result line,
``"spans"``:

* ``sync_idle_ms`` / ``launch_idle_ms`` (``--trace 1``): over the
  ``engine.step`` spans that lie inside the traced window, the median of
  each step's device-idle time inside its ``engine.sync`` span, and of
  its device-idle time outside it.  Their sum is the program's side of
  the harness's ``step_host_ms`` (the idle time under ``bench.step``).
* ``idle_by_span_s`` / ``longest_gaps`` (``--trace 1``): the window's
  device-idle gaps, each labelled by the innermost ``engine.*`` or
  ``bench.*`` span that holds the gap's midpoint.
* ``modules`` (``--trace 1``): the names on the device's ``XLA Modules``
  line; ``unnested`` counts the window's ``engine.*`` spans that no
  ``bench.step`` or ``bench.admit`` holds.
* ``queue_wait_p90_ms`` / ``prefill_wait_p90_ms`` (every run): over the
  requests due in the measured window, the 90th percentile of
  ``t_admit - due`` (``t1 - due`` when not admitted by the window's end
  ``t1``) and, for those admitted, of ``t_first - t_admit``
  (``t1 - t_admit`` with no first token by ``t1``).

``--spans 0`` leaves the engine's null observer: an untraced pair with
``--spans 1`` on one seed gives the cost of spans on.

``bench.harness.run`` reduces its trace and deletes it before returning;
this script wraps ``bench.trace.summarize`` so that the same file is read
a second time, for the engine's spans, before it goes.  Nothing the
harness reports changes.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import trace as trace_lib  # noqa: E402

PREFIXES = ("engine.", "bench.")
OUTER = ("bench.step", "bench.admit")


def host_spans(planes, mark_host: float) -> list:
    """The host's ``engine.*`` and ``bench.*`` spans as (name, start,
    duration, args), in seconds on the ``perf_counter`` axis the harness
    stamps with (through its ``bench.mark`` span, as ``bench.trace``)."""
    evs, mark = [], None
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == trace_lib.MARK and mark is None:
                    mark = ev.start_ns
                if ev.name.startswith(PREFIXES):
                    evs.append((ev.name, ev.start_ns, ev.duration_ns,
                                dict(ev.stats)))
    if mark is None:
        raise ValueError("trace holds no bench.mark span")
    return [(n, (s - mark) * 1e-9 + mark_host, d * 1e-9, a)
            for n, s, d, a in evs]


def module_names(planes) -> list:
    """The executables launched on the device, by name."""
    names = set()
    for plane in planes:
        if trace_lib.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == trace_lib.MODULES_LINE:
                    names |= {trace_lib.module_name(e.name)
                              for e in line.events}
    return sorted(names)


def _inside(spans, name, s0, s1) -> list:
    return [(s, d) for n, s, d, _ in spans
            if n == name and s0 <= s and s + d <= s1]


def idle_split(summary, spans) -> dict:
    """Per-step device-idle time inside and outside ``engine.sync``."""
    sync_idle, launch_idle = [], []
    for s, d in _inside(spans, "engine.step", summary.host_t0,
                        summary.host_t1):
        si = sum(summary.uncovered(a, ad)
                 for a, ad in _inside(spans, "engine.sync", s, s + d))
        sync_idle.append(si)
        launch_idle.append(summary.uncovered(s, d) - si)
    if not sync_idle:
        return {"engine_steps": 0}
    return {"sync_idle_ms": statistics.median(sync_idle) * 1e3,
            "launch_idle_ms": statistics.median(launch_idle) * 1e3,
            "engine_steps": len(sync_idle)}


def idle_gaps(summary, spans) -> list:
    """The window's device-idle gaps as (seconds, label): the innermost
    span that holds the gap's midpoint, ``none`` outside every span."""
    t0, t1 = summary.host_t0, summary.host_t1
    edges = [[t0, t0]] + summary.busy + [[t1, t1]]
    out = []
    for (_, e0), (s1, _) in zip(edges, edges[1:]):
        a, b = max(e0, t0), min(s1, t1)
        if b <= a:
            continue
        mid = (a + b) / 2
        holding = [(d, n) for n, s, d, _ in spans
                   if n != trace_lib.MARK and s <= mid <= s + d]
        out.append((b - a, min(holding)[1] if holding else "none"))
    return sorted(out, reverse=True)


def unnested(spans, t0: float, t1: float) -> int:
    """The window's ``engine.*`` spans that no harness span holds."""
    outer = [(s, s + d) for n, s, d, _ in spans if n in OUTER]
    return sum(1 for n, s, d, _ in spans
               if n.startswith("engine.") and t0 <= s and s + d <= t1
               and not any(o0 <= s and s + d <= o1 for o0, o1 in outer))


def split(summary, planes, mark_host: float) -> dict:
    spans = host_spans(planes, mark_host)
    gaps = idle_gaps(summary, spans)
    by_label: dict = {}
    for g, label in gaps:
        by_label[label] = by_label.get(label, 0.0) + g
    out = idle_split(summary, spans)
    out.update(idle_by_span_s=by_label, longest_gaps=gaps[:8],
               modules=module_names(planes),
               unnested=unnested(spans, summary.host_t0, summary.host_t1),
               engine_spans=sum(n.startswith("engine.")
                                for n, _, _, _ in spans))
    return out


def waits(data, reqs: dict) -> dict:
    """Queue wait and admission-to-first-token of the window's requests,
    from the program's own ``t_admit`` / ``t_first`` stamps."""
    queue, prefill = [], []
    for rec in data.recs:
        if not data.t0 <= rec.due < data.t1:
            continue
        req = reqs[rec.rid]
        admit = req.t_admit if (req.t_admit is not None
                                and req.t_admit < data.t1) else None
        queue.append((data.t1 if admit is None else admit) - rec.due)
        if admit is not None:
            first = req.t_first if (req.t_first is not None
                                    and req.t_first < data.t1) else data.t1
            prefill.append(first - admit)
    p90 = lambda x: float(np.percentile(x, 90)) * 1e3 if x else None  # noqa
    return {"queue_wait_p90_ms": p90(queue),
            "prefill_wait_p90_ms": p90(prefill), "window_requests": len(queue)}


def attach(engine, observer) -> None:
    """Point every layer of a built engine at ``observer``."""
    engine.obs = engine.sched.obs = observer
    for layer in (getattr(engine, "alloc", None),
                  getattr(engine, "drafter", None)):
        if layer is not None:
            layer.obs = observer
    observer.register_census(lambda: engine.compilations)


def main(argv=None) -> int:
    from bench import harness
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--spans", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    try:
        harness.require_chip(cell)
    except harness.NoChip as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import use_compile_cache
    from repro.launch.serve import profiler_span
    from repro.obs.runtime import Observer
    use_compile_cache()

    def hook(engine):
        if args.spans:
            attach(engine, Observer(span=profiler_span))

    extra: dict = {}
    summarize = trace_lib.summarize

    def summarize_and_split(path, h0, h1, mark_host):
        summary = summarize(path, h0, h1, mark_host)
        extra.update(split(summary, list(trace_lib.load(path).planes),
                           mark_host))
        return summary

    seen: dict = {}
    trace_lib.summarize = summarize_and_split
    try:
        result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                             T_START, engine_hook=hook, inspect=seen.update)
    finally:
        trace_lib.summarize = summarize
    extra.update(waits(seen["data"], seen["reqs"]))
    result["spans"] = extra
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
