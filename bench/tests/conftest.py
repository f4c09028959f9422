"""Shared set-up of the benchmark's own tests (run with
``python -m pytest bench/tests``): the benchmark's and the program's
directories on the path, and cells cut to a size the CPU holds."""
import dataclasses
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (BENCH, BENCH / "systems", ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import harness  # noqa: E402

CELLS = ("paper-cg", "resident-cg", "resident-mvm-b64")


def small_cell(name: str, n: int = 1024, capacity: int = 256,
               cell: int = 128) -> harness.Cell:
    """The cell as committed, its matrix cut to ``n`` on smaller blocks
    (the widths of the simulation, its device and its EC, unchanged)."""
    full = harness.load_cell(name)
    conf = dict(full.config, n=n, capacity=capacity, cell_rows=cell,
                cell_cols=cell, matrix=dict(full.config["matrix"], seed=n))
    return dataclasses.replace(full, config=conf)


def run_small(name: str, seed: int = 11, seconds: float = 0.5,
              cell: harness.Cell = None, **kw):
    return harness.run_cell(name, seed, seconds, False, t0=0.0,
                            require_tpu=False,
                            cell=cell or small_cell(name), **kw)


@pytest.fixture(params=CELLS)
def cell_name(request):
    return request.param
