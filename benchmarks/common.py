"""Shared benchmark utilities: CSV rows and JSON metadata."""
from __future__ import annotations

import os
from typing import Dict, List

import jax


def run_metadata() -> Dict:
    """Environment fingerprint every benchmark JSON artifact must embed.

    Records the *initialized* device count and the ``XLA_FLAGS`` that shaped
    it: the scaling benchmarks force an 8-host-device backend at import
    (``--xla_force_host_platform_device_count=8``), which would otherwise
    silently confound a future perf-baseline refresh comparing against
    numbers collected under a different device topology (the ROADMAP item).
    """
    return {
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
        "jax_version": jax.__version__,
    }


def emit(rows: List[Dict]) -> None:
    for r in rows:
        name = r.pop("name")
        derived = ";".join(f"{k}={v}" for k, v in r.items())
        print(f"{name},{derived}")
