"""Linear programming on ONE programmed crossbar image (PDHG).

The companion RRAM-PDHG paper's regime: a standard-form LP

    min c'x   s.t.   A x = b,  x >= 0

is solved by the primal-dual hybrid gradient method, which touches the
constraint matrix only through ``A @ x`` and ``A.T @ y``.  Both directions
read the SAME conductance image -- the matrix is programmed exactly once and
every PDHG iteration (one corrected forward MVM + one corrected TRANSPOSED
MVM) amortizes that write, with forward and transposed input-write costs
billed separately in the :class:`~repro.solvers.SolveLedger`.

The LP is generated with a KNOWN optimal primal-dual pair
(:func:`repro.solvers.random_feasible_lp`), so the example reports the true
objective gap of both the digital PDHG oracle and the analog solve.

``--mesh R,C`` distributes the solve: the image is block-sharded over the
mesh, the forward MVM psums over the contraction columns (output
row-sharded), the transposed MVM psums over the ROWS (output column-sharded)
-- so the whole jitted PDHG while_loop keeps its x/y panels sharded with no
gathers.  ``--producer`` programs through a traceable ``block_fn(i, j)``
producer instead of the dense array.

    PYTHONPATH=src python examples/meliso_lp.py
    PYTHONPATH=src python examples/meliso_lp.py --n 1024 --m 768
    PYTHONPATH=src python examples/meliso_lp.py --mesh 2,4 --producer
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse

import jax
import jax.numpy as jnp

from repro import solvers
from repro.core import CrossbarConfig, MCAGeometry, get_device, rel_l2
from repro.engine import AnalogEngine
from repro.launch.mesh import make_mesh
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--m", type=int, default=256, help="LP constraints (rows)")
    ap.add_argument("--n", type=int, default=512, help="LP variables (cols)")
    ap.add_argument("--tol", type=float, default=2e-4,
                    help="KKT-residual stopping tolerance")
    ap.add_argument("--maxiter", type=int, default=20000)
    ap.add_argument("--device", default="epiram")
    ap.add_argument("--cell", type=int, default=64)
    ap.add_argument("--mesh", default="1,1", metavar="R,C",
                    help="mesh shape (1,1 = single device)")
    ap.add_argument("--producer", action="store_true",
                    help="program through a block producer (the distributed "
                         "scan-programmed pipeline)")
    args = ap.parse_args()

    try:
        rows, cols = (int(v) for v in args.mesh.split(","))
    except ValueError:
        raise SystemExit(f"--mesh must be 'R,C' integers, got {args.mesh!r}")
    if rows * cols > jax.device_count():
        raise SystemExit(
            f"--mesh {rows}x{cols} needs {rows * cols} devices but only "
            f"{jax.device_count()} are available")

    key = jax.random.PRNGKey(0)
    a, b, c, x_star, y_star = solvers.random_feasible_lp(
        key, args.m, args.n)
    obj_star = float(c @ x_star)

    geom = MCAGeometry(tile_rows=1, tile_cols=1,
                       cell_rows=args.cell, cell_cols=args.cell)
    cfg = CrossbarConfig(device=get_device(args.device), geom=geom,
                         k_iters=5, ec=True)
    if rows * cols == 1:
        engine = AnalogEngine(cfg)
        A = engine.program(a, key)
    else:
        mesh = make_mesh((rows, cols), ("data", "model"))
        engine = AnalogEngine(cfg, execution="distributed", mesh=mesh)
        if args.producer:
            cap_m, cap_n = cfg.geom.capacity
            mb, nb = -(-args.m // cap_m), -(-args.n // cap_n)
            a_pad = jnp.pad(a, ((0, mb * cap_m - args.m),
                                (0, nb * cap_n - args.n)))
            blocks = a_pad.reshape(mb, cap_m, nb, cap_n).transpose(0, 2, 1, 3)
            A = engine.program(lambda i, j: blocks[i, j], key,
                               shape=a.shape)
        else:
            A = engine.program(a, key)

    print(f"LP: {args.m} constraints x {args.n} vars, device={args.device}, "
          f"mesh={args.mesh}, producer={args.producer}")
    print(f"known optimum c'x* = {obj_star:.6f} (= b'y* = "
          f"{float(b @ y_star):.6f})")
    print(f"one-time write energy = {float(A.write_stats.energy_j):.3e} J\n")

    # Oracle: the same algorithm on the exact digital operator, run to the
    # same tolerance (PDHG is O(1/k); a much tighter digital tol would just
    # burn iterations without changing the comparison).
    digital = solvers.pdhg(a, b, c, tol=args.tol, maxiter=args.maxiter)
    analog = solvers.pdhg(A, b, c, tol=args.tol, maxiter=args.maxiter,
                          key=key)

    print(f"{'solver':20s} {'iters':>6s} {'kkt':>9s} {'objective':>11s} "
          f"{'gap to *':>9s} {'E_write J':>10s} {'E_iters J':>10s}")
    for name, res in (("pdhg digital", digital), ("pdhg analog", analog)):
        obj = float(c @ res.x)
        gap = abs(obj - obj_star) / (1 + abs(obj_star))
        led = res.ledger
        print(f"{name:20s} {res.iterations:6d} {res.final_residual:9.2e} "
              f"{obj:11.6f} {gap:9.2e} {led.write_energy_j:10.3e} "
              f"{led.iteration_energy_j:10.3e}")

    obj_a, obj_d = float(c @ analog.x), float(c @ digital.x)
    obj_gap = abs(obj_a - obj_d) / (1 + abs(obj_d))
    assert analog.converged and digital.converged
    assert obj_gap <= 1e-3, (obj_a, obj_d)
    assert float(rel_l2(a @ analog.x, b)) < 10 * args.tol

    led = analog.ledger
    print(f"\nledger: {led.mvms} forward MVMs @ "
          f"{float(led.input_stats.energy_j):.3e} J + {led.mvms_t} "
          f"transposed MVMs @ {float(led.input_stats_t.energy_j):.3e} J + "
          f"{led.mvms_single}+{led.mvms_single_t} setup MVMs, one matrix write "
          f"{led.write_energy_j:.3e} J")
    print(f"analog objective within {obj_gap:.1e} of the digital oracle")


if __name__ == "__main__":
    main()
