#!/usr/bin/env python
"""Dispatch-count gate: fresh benchmark rows vs the checked-in baselines.

Re-runs a benchmark module in its quick/smoke mode and compares every row
that also exists in the checked-in ``BENCH_<name>.json`` (matched by row
``name``): every ``dispatch*``-keyed field must match the baseline EXACTLY.
Dispatch structure is topology-independent: a PR that silently
re-introduces per-layer or per-block launches fails here on any machine.
Speed is measured on the chip by the benchmark under ``bench/``, not here.

Rows present only in the fresh run (or only in the full-sweep baseline --
smoke sweeps a subset) are ignored: the gate compares trajectories, it does
not require identical sweeps.

Usage:

    PYTHONPATH=src python tools/check_perf.py                   # all gated
    PYTHONPATH=src python tools/check_perf.py --bench model_dispatch
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

#: benchmarks gated here: checked-in baseline -> module with a run(quick=)
#: entry point whose quick rows share names with the full-sweep baseline.
GATED = {
    "model_dispatch": "benchmarks.model_dispatch",
    "streamed_scaling": "benchmarks.streamed_scaling",
}


def _baseline(name: str) -> dict:
    path = ROOT / f"BENCH_{name}.json"
    with open(path) as f:
        return json.load(f)


def _dispatch_keys(row: dict):
    return sorted(k for k in row if "dispatch" in k)


def check_bench(name: str, module: str) -> list:
    """Returns a list of violation strings for one gated benchmark."""
    base = _baseline(name)
    fresh_rows = importlib.import_module(module).run(quick=True)
    base_rows = {r["name"]: r for r in base["rows"]}

    violations = []
    compared = 0
    for row in fresh_rows:
        ref = base_rows.get(row["name"])
        if ref is None:
            continue
        compared += 1
        for k in _dispatch_keys(ref):
            if row.get(k) != ref[k]:
                violations.append(
                    f"{name}/{row['name']}: {k} = {row.get(k)} "
                    f"(baseline {ref[k]}) -- dispatch structure changed")
    if not compared:
        violations.append(
            f"{name}: no fresh row matches the baseline -- sweep renamed?")
    print(f"[perf] {name}: {compared} rows compared, "
          f"{len(violations)} violation(s)")
    return violations


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--bench", default=None,
                    help="gate only this benchmark (default: all gated)")
    args = ap.parse_args()

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    names = [args.bench] if args.bench else sorted(GATED)
    violations = []
    for name in names:
        if name not in GATED:
            print(f"[perf] unknown benchmark {name!r}; gated: "
                  f"{sorted(GATED)}")
            return 2
        violations += check_bench(name, GATED[name])
    for v in violations:
        print(f"[perf] FAIL {v}")
    if violations:
        return 1
    print("perf OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
