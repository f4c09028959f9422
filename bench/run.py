"""Run one benchmark cell once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cells, their configurations, mixes and metrics are named in
``BENCHMARK.json`` at the root of the checkout; ``bench/harness.py`` says
what one run does.  Exits non-zero, printing no result, where JAX finds no
TPU or fewer chips than the cell asks for.
"""
import time

T0 = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t0=T0, age0=harness.process_age_s()))
