"""Readings that set a cell's fixed numbers, taken on the chip once per
benchmark change, never in a benchmark run.

    python bench/calibrate.py limits --workload W --seeds 1 2 ... --seconds S
    python bench/calibrate.py knee --workload W --rates 0.5 1 1.5 --seconds S

``limits`` runs the cell once per seed in this one process (each run
builds its engine and weights from its seed and frees them) and scores
the sampled served tokens against the float32 reference, and the tokens
the fp8 and int8 controls put first at each of those positions.  The
largest program gap over the seeds is the limit's lower reading, the
smallest fp8 control gap its upper one (``bench/checks/<workload>.json``).
Each control also goes through the run's own decision against the cell's
limit, and its verdict (``fp8_correct``) has to come out false.

``knee`` offers the cell's open-loop mix at each rate and prints what the
engine completed and how its queue grew: the highest rate at which the
completions keep up with the arrivals is the knee, and the cell's mix
offers a fixed fraction of it.

Each reading is one JSON line on standard output; the run's own last
line and checks go to standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

CONTROLS = ("fp8", "int8")   # fp8 is the limit's control, int8 for reference


def limits(cell, seeds, seconds):
    for seed in seeds:
        res = harness.run(cell, seed, seconds, False, time.perf_counter(),
                          controls=CONTROLS)
        harness.print_result(res, out=sys.stderr)
        chk = res["checks"]
        print(json.dumps({
            "workload": cell.name, "seed": seed,
            "program_gap": chk["logit_gap"]["value"],
            **{f"{c}_{k}": chk.get(f"control_{c}_logit_gap", {}).get(v)
               for c in CONTROLS for k, v in (("gap", "value"),
                                              ("correct", "correct"))},
            "tokens": chk["logit_gap"]["tokens"],
            "requests": chk["logit_gap"]["requests"],
            "attempted": res["attempted"], "failed": res["failed"]}),
            flush=True)


def knee(cell, rates, seconds, seed):
    for rate in rates:
        c = dataclasses.replace(cell, mix=dict(cell.mix, rate_per_s=rate))
        seen = {}
        res = harness.run(c, seed, seconds, False, time.perf_counter(),
                          inspect=seen.update)
        harness.print_result(res, out=sys.stderr)
        d = seen["data"]
        due = [r for r in d.recs if r.due < d.t1]
        done = [r for r in d.recs if r.end is not None and d.t0 <= r.end <= d.t1]
        waiting_at = lambda t: sum(1 for r in due if r.due <= t and  # noqa: E731
                                   (r.first is None or r.first > t))
        print(json.dumps({
            "workload": cell.name, "rate_per_s": rate,
            "completed_per_s": len(done) / d.seconds,
            "arrived_per_s": res["attempted"] / d.seconds,
            "waiting_at_open": waiting_at(d.t0),
            "waiting_at_close": waiting_at(d.t1),
            "metrics": {k: v["value"] for k, v in res["metrics"].items()}}),
            flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("what", choices=("limits", "knee"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[1])
    ap.add_argument("--rates", type=float, nargs="*", default=[])
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    try:
        harness.require_chip(cell)
    except harness.NoChip as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    if args.what == "limits":
        limits(cell, args.seeds, args.seconds)
    else:
        knee(cell, args.rates, args.seconds, args.seeds[0])
    return 0


if __name__ == "__main__":
    sys.exit(main())
