"""Paper Fig. 4 (weak scaling): fixed problem (add32 surrogate, 4960^2),
fixed 8x8 MCA tile, array cell size swept 32^2 .. 1024^2.

Expected (paper section 2.3.1): relative error stays flat (~1e-3..4e-2 band);
small cells pay heavily in write energy/latency because virtualization
reassigns each MCA ceil(4960/(8*cell))^2 times; >=512^2 cells execute in one
assignment.

:func:`run_distributed` adds the mesh dimension of weak scaling: a FIXED
per-device window of the capacity-block grid, mesh grown 1 -> 4 -> 8 devices
(problem size grows with it), each point programmed from a traceable block
producer -- the matrix never materializes -- and driven through a distributed
CG solve, which must converge at every point.

    PYTHONPATH=src python -m benchmarks.weak_scaling --smoke     # CI fast job
"""
from __future__ import annotations

import os
# Must precede backend init so the standalone CLI gets a multi-device mesh.
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro import solvers
from repro.core import (CrossbarConfig, MCAGeometry, get_device,
                        rel_l2, rel_linf)
from repro.core.matrices import ImplicitBandedMatrix, make_spd_with_condition
from repro.core.virtualization import reassignment_count
from repro.engine import AnalogEngine
from repro.launch.mesh import make_mesh

N = 4960   # add32 dimension


def run(quick: bool = True) -> List[Dict]:
    cells = [32, 128, 512, 1024] if quick else [32, 64, 128, 256, 512, 1024]
    devices = ["taox-hfox", "epiram"] if quick else [
        "epiram", "ag-si", "alox-hfo2", "taox-hfox"]
    a = jnp.asarray(
        make_spd_with_condition(N, kappa=1.366769e2, norm2=5.749318e-2),
        jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (N,))
    b = a @ x
    rows = []
    for cell in cells:
        geom = MCAGeometry(tile_rows=8, tile_cols=8,
                           cell_rows=cell, cell_cols=cell)
        for dev in devices:
            cfg = CrossbarConfig(device=get_device(dev), geom=geom,
                                 k_iters=5, ec=True)
            engine = AnalogEngine(cfg)
            A = engine.program(a, jax.random.PRNGKey(cell))
            y = engine.mvm(A, x)
            per_call = A.input_write_stats(batch=1)
            rows.append({
                "name": f"weak/{dev}/cell{cell}",
                "eps_l2": float(rel_l2(y, b)),
                "eps_linf": float(rel_linf(y, b)),
                "E_w": float(A.write_stats.energy_j) + float(per_call.energy_j),
                "L_w": float(A.write_stats.latency_s) + float(per_call.latency_s),
                "reassignments": reassignment_count(N, N, geom),
            })
    rows += run_distributed(quick=quick)
    return rows


def run_distributed(quick: bool = True) -> List[Dict]:
    """Mesh weak scaling: ~fixed per-device block window, growing device grid.

    Each mesh point programs an :class:`ImplicitBandedMatrix` over the mesh
    from its block producer and runs a CG solve.  The square-grid constraint
    makes an exactly fixed window impossible at 8 devices, so the 2x4 point
    carries HALF the window; every row reports ``blocks_per_dev``.
    """
    cap = 128 if quick else 512
    # (mesh shape, square block-grid edge): grid g x g with g chosen so every
    # device owns an equal window (g % rows == 0, g % cols == 0).  1 -> 4
    # devices holds 4 blocks/device; 8 devices halves it (see docstring).
    points = [((1, 1), 2), ((2, 2), 4), ((2, 4), 4)]
    avail = jax.device_count()
    rows: List[Dict] = []
    for shape, g in points:
        n_dev = shape[0] * shape[1]
        if n_dev > avail:
            continue
        mesh = make_mesh(shape, ("data", "model"))
        n = g * cap
        geom = MCAGeometry(tile_rows=1, tile_cols=1,
                           cell_rows=cap, cell_cols=cap)
        cfg = CrossbarConfig(device=get_device("epiram"), geom=geom,
                             k_iters=5, ec=True)
        eng = AnalogEngine(cfg, execution="distributed", mesh=mesh)
        imp = ImplicitBandedMatrix(n=n, cap_m=cap, cap_n=cap, seed=g)
        key = jax.random.fold_in(jax.random.PRNGKey(7), n_dev)
        A = eng.program(imp.block, key, shape=(n, n))
        res = solvers.cg(A, jnp.ones((n,), jnp.float32), tol=5e-3,
                         maxiter=12, key=key)
        rows.append({
            "name": f"weak/dist/mesh{shape[0]}x{shape[1]}/n{n}",
            "devices": n_dev,
            "blocks_per_dev": (g * g) // n_dev,
            "iters": res.iterations,
            "converged": bool(res.converged),
            "resid": res.final_residual,
        })
    return rows


if __name__ == "__main__":
    import argparse

    from .common import emit
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI fast mode: only the distributed mesh sweep")
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    if args.smoke:
        emit(run_distributed(quick=True))
    else:
        emit(run(quick=not args.full))
