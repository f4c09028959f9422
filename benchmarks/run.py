"""Benchmark entry point: one module per paper table/figure (+ the LM-step
framework bench).  Prints ``name,us_per_call,derived`` CSV.

    PYTHONPATH=src python -m benchmarks.run            # quick (CI) mode
    PYTHONPATH=src python -m benchmarks.run --full     # full paper protocol
    PYTHONPATH=src python -m benchmarks.run --only table1
"""
from __future__ import annotations

import argparse
import sys
import time

from repro.launch.compile_cache import enable_compile_cache

from .common import emit


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale replication counts / sizes")
    ap.add_argument("--only", default=None,
                    help="substring filter on benchmark module name")
    args = ap.parse_args()
    quick = not args.full

    from . import (lm_step, lstsq_convergence, model_dispatch,
                   pdhg_convergence, reliability, serving, solver_convergence,
                   streamed_scaling, strong_scaling, table1_ec, weak_scaling,
                   writeverify_sweep)
    modules = [
        ("table1_ec", table1_ec),
        ("writeverify_sweep", writeverify_sweep),
        ("solver_convergence", solver_convergence),
        ("pdhg_convergence", pdhg_convergence),
        ("lstsq_convergence", lstsq_convergence),
        ("weak_scaling", weak_scaling),
        ("strong_scaling", strong_scaling),
        ("streamed_scaling", streamed_scaling),
        ("model_dispatch", model_dispatch),
        ("lm_step", lm_step),
        ("serving", serving),
        ("reliability", reliability),
    ]
    print("name,us_per_call,derived")
    for name, mod in modules:
        if args.only and args.only not in name:
            continue
        t0 = time.perf_counter()
        rows = mod.run(quick=quick)
        emit(rows)
        print(f"# {name}: {time.perf_counter() - t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
