"""Streamed single-dispatch scaling: scan-fused pipeline vs the host loop.

The paper's scalability showcase (matrices beyond 65,000^2) executes MVMs
block-by-block against a streamed producer.  Pre-scan, that was a Python
double loop -- O(mb * nb) host->device dispatches per MVM, re-paid every
solver iteration -- so the framework was dispatch-bound long before it was
compute-bound.  This benchmark sweeps the capacity-block count and reports,
for the same producer and keys, the scan-fused pipeline (ONE dispatch/MVM)
against the compat host loop (mb * nb dispatches, forced via an explicit
``traceable = False`` marker):

  * dispatches per MVM of each path;
  * producer invocations per *warm* MVM (0 scanned vs mb * nb looped) -- the
    host-work proxy for the dispatch count;
  * ``rel_l2``   -- parity between the two paths (same keys => same draws).

Results land in ``BENCH_streamed_scaling.json`` at the repo root (checked in,
so later PRs can compare against this trajectory).

    PYTHONPATH=src python -m benchmarks.streamed_scaling            # full sweep
    PYTHONPATH=src python -m benchmarks.streamed_scaling --smoke    # CI fast job
"""
from __future__ import annotations

import argparse
import json
import os
import tempfile
from typing import Dict, List

import jax

from repro.core import CrossbarConfig, MCAGeometry, get_device, rel_l2
from repro.core.matrices import ImplicitBandedMatrix
from repro.engine import AnalogEngine

from .common import run_metadata

CAP = 32                                   # capacity block edge (1x1 tile MCA)
GEOM = MCAGeometry(tile_rows=1, tile_cols=1, cell_rows=CAP, cell_cols=CAP)
GRIDS_FULL = [2, 4, 8, 16]                 # nb x nb capacity blocks
GRIDS_SMOKE = [2, 4]
OUT_JSON = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_streamed_scaling.json")


def _counting(fn):
    calls = {"n": 0}

    def wrapped(i, j):
        calls["n"] += 1
        return fn(i, j)

    return wrapped, calls


def _bench_grid(nb: int, cfg: CrossbarConfig) -> Dict:
    n = nb * CAP
    key = jax.random.fold_in(jax.random.PRNGKey(42), n)
    imp = ImplicitBandedMatrix(n=n, cap_m=CAP, cap_n=CAP, seed=nb)
    x = jax.random.normal(jax.random.fold_in(key, 1), (n,))

    # Scan-fused pipeline: the producer is traceable, so program and every
    # MVM are single dispatches.
    scan_fn, scan_calls = _counting(imp.block)
    eng_scan = AnalogEngine(cfg, execution="streamed")
    A_scan = eng_scan.program(scan_fn, key, shape=(n, n))
    assert A_scan.block_traceable

    # Pre-PR regime: identical producer/keys, host loop forced per block.
    loop_fn, loop_calls = _counting(imp.block)
    loop_fn.traceable = False
    eng_loop = AnalogEngine(cfg, execution="streamed")
    A_loop = eng_loop.program(loop_fn, key, shape=(n, n))
    assert not A_loop.block_traceable

    k_mvm = jax.random.fold_in(key, 2)
    for eng, A in ((eng_scan, A_scan), (eng_loop, A_loop)):    # warm up
        jax.block_until_ready(eng.mvm(A, x, key=k_mvm))

    # Host-work per warm MVM (the dispatch-count proxy): one measured call.
    c0 = scan_calls["n"]
    y_scan = eng_scan.mvm(A_scan, x, key=k_mvm)
    scan_per_mvm = scan_calls["n"] - c0
    c0 = loop_calls["n"]
    y_loop = eng_loop.mvm(A_loop, x, key=k_mvm)
    loop_per_mvm = loop_calls["n"] - c0

    return {
        "name": f"streamed_scaling/grid{nb}x{nb}/n{n}",
        "n": n,
        "blocks": nb * nb,
        "producer_calls_per_mvm_scan": scan_per_mvm,
        "producer_calls_per_mvm_loop": loop_per_mvm,
        "dispatches_per_mvm_scan": 1,
        "dispatches_per_mvm_loop": nb * nb,
        "rel_l2_scan_vs_loop": float(rel_l2(y_scan, y_loop)),
    }


def run(quick: bool = True) -> List[Dict]:
    cfg = CrossbarConfig(device=get_device("taox-hfox"), geom=GEOM,
                         k_iters=5, ec=True)
    grids = GRIDS_SMOKE if quick else GRIDS_FULL
    rows = [_bench_grid(nb, cfg) for nb in grids]
    _write_json(rows, quick)
    return rows


def _out_path(quick: bool) -> str:
    """Full sweeps refresh the checked-in trajectory file at the repo root;
    quick/smoke runs (CI, ``benchmarks.run`` default) write to the temp dir
    so they never clobber the committed full-sweep baseline."""
    if quick:
        return os.path.join(tempfile.gettempdir(),
                            "BENCH_streamed_scaling.smoke.json")
    return OUT_JSON


def _write_json(rows: List[Dict], quick: bool) -> str:
    payload = {
        "bench": "streamed_scaling",
        "mode": "smoke" if quick else "full",
        "metadata": run_metadata(),
        "geom": {"cap": CAP, "tiles": [1, 1]},
        "rows": rows,
    }
    out = _out_path(quick)
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="small grids (CI fast job); "
                         "writes to the temp dir, leaving the checked-in "
                         "full-sweep JSON untouched")
    args = ap.parse_args()
    rows = run(quick=args.smoke)
    for r in rows:
        print(f"{r['name']}: {r['dispatches_per_mvm_loop']} dispatches "
              f"looped vs 1 scanned, parity {r['rel_l2_scan_vs_loop']:.2e}")
    print(f"wrote {_out_path(args.smoke)}")


if __name__ == "__main__":
    main()
