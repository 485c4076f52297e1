"""Benchmark harness — one module per paper table (+ roofline/kernels).
Prints ``name,us_per_call,derived`` CSV rows."""
from __future__ import annotations

import sys


def main() -> None:
    from benchmarks import (analytical_validation, kernels_bench,
                            roofline_report, table1_sweep, table2_baselines,
                            table34_accelerators)
    only = sys.argv[1] if len(sys.argv) > 1 else None
    mods = {
        "table1": table1_sweep,
        "table2": table2_baselines,
        "table34": table34_accelerators,
        "analytical": analytical_validation,
        "kernels": kernels_bench,
        "roofline": roofline_report,
    }
    for name, mod in mods.items():
        if only and name != only:
            continue
        mod.run()


if __name__ == '__main__':
    main()
