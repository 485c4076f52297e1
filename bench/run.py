"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload qwen2-7b.chat --seed 7 --seconds 30 --trace 0

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of a sub-window.  The run refuses
to start (exit 2, no result) unless JAX's first device is a TPU and it
sees as many chips as the cell asks for.  The last lines of standard
error give each number the correctness check compared, beside its limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    try:
        harness.require_chip(cell)
    except harness.NoChip as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         T_START)
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
