"""Solver convergence/energy sweep: device x EC on/off x solver.

For each RRAM device model, with and without the two-tier error correction,
runs the ``repro.solvers`` methods against one programmed image of an SPD
system and reports

  * iterations-to-tolerance (NaN-free count actually executed),
  * the final relative residual and true solution error,
  * joules-per-solve, split into the one-time programming energy and the
    accumulated per-MVM input-write energy (the amortization the paper's
    program-once model buys).

Quick mode (CI) solves a 128-dim system with the matvec-only trio
(richardson / cg / bicgstab); full mode grows the system, adds gmres +
mixed-precision refinement, and sweeps all four devices.

    PYTHONPATH=src python -m benchmarks.run --only solver
    PYTHONPATH=src python -m benchmarks.solver_convergence --full
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp

from repro import solvers
from repro.core import CrossbarConfig, MCAGeometry, get_device, rel_l2
from repro.engine import AnalogEngine

QUICK_DEVICES = ["epiram", "taox-hfox"]
FULL_DEVICES = ["epiram", "ag-si", "alox-hfo2", "taox-hfox"]


def _spd_system(n: int, key: jax.Array):
    r = jax.random.normal(key, (n, n), jnp.float32) / n
    a = r + r.T + 2.0 * jnp.eye(n, dtype=jnp.float32)
    x_true = jax.random.normal(jax.random.fold_in(key, 1), (n,), jnp.float32)
    return a, x_true, a @ x_true


def _solver_menu(quick: bool):
    menu = [
        ("richardson", lambda A, b, tol, it:
            solvers.richardson(A, b, tol=tol, maxiter=it)),
        ("cg", lambda A, b, tol, it: solvers.cg(A, b, tol=tol, maxiter=it)),
        ("bicgstab", lambda A, b, tol, it:
            solvers.bicgstab(A, b, tol=tol, maxiter=it)),
    ]
    if not quick:
        menu += [
            ("gmres", lambda A, b, tol, it:
                solvers.gmres(A, b, tol=tol, maxiter=it, restart=10)),
            ("refine_cg", lambda A, b, tol, it:
                solvers.refine(A, b, tol=tol, maxiter=it, inner_iters=6)),
        ]
    return menu


def run(quick: bool = True) -> List[Dict]:
    n = 128 if quick else 512
    cell = 32 if quick else 64
    tol = 1e-3
    maxiter = 40 if quick else 80
    key = jax.random.PRNGKey(0)
    a, x_true, b = _spd_system(n, key)
    geom = MCAGeometry(tile_rows=2, tile_cols=2, cell_rows=cell,
                       cell_cols=cell)
    rows: List[Dict] = []
    for dev in (QUICK_DEVICES if quick else FULL_DEVICES):
        for ec in (False, True):
            cfg = CrossbarConfig(device=get_device(dev), geom=geom,
                                 k_iters=5, ec=ec)
            engine = AnalogEngine(cfg)
            A = engine.program(a, jax.random.fold_in(key, 7))
            for sname, solve in _solver_menu(quick):
                res = solve(A, b, tol, maxiter)
                led = res.ledger
                rows.append({
                    "name": f"solver/{dev}/{'ec' if ec else 'raw'}/{sname}",
                    "iters": res.iterations,
                    "converged": res.converged,
                    "resid": f"{res.final_residual:.3e}",
                    "x_err": f"{float(rel_l2(res.x, x_true)):.3e}",
                    "mvms": led.mvms,
                    "E_write_J": f"{led.write_energy_j:.3e}",
                    "E_iters_J": f"{led.iteration_energy_j:.3e}",
                    "E_total_J": f"{led.total_energy_j:.3e}",
                })
    return rows


if __name__ == "__main__":
    import argparse

    from .common import emit

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    print("name,derived")
    emit(run(quick=not args.full))
