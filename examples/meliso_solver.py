"""Distributed MELISO+ solve through the ``repro.solvers`` subsystem.

A large SPD matrix is programmed ONCE across a device mesh (rows shard over
'data', the contraction over 'model'; each device keeps its block of the
conductance image resident), then *reused* by matvec-only iterative solvers:

  * the legacy fixed-omega Richardson loop (omega = 1/3, what this example
    hand-rolled before the solver layer existed) as the baseline;
  * Richardson with auto-omega from a matvec-only power-iteration spectral
    estimate;
  * conjugate gradients.

Every solver iteration re-executes against the SAME programmed image -- tier-1
EC locally, psum partials, denoise on-node -- so the one-time write cost
amortizes across the whole solve (the PDHG-style regime of the companion
papers), and each ``SolveResult`` ledger splits energy into the one-time
programming cost vs the per-iteration input-write cost.

``--mesh R,C`` picks the placement (R row shards x C contraction shards;
``1,1`` runs the whole solve on one device -- draw-identical to the streamed
path).  ``--producer`` programs through a traceable ``block_fn(i, j)``
producer instead of the dense array: each device scan-programs only its
window of the global block grid.  Note this example's producer reads the
dense copy that exists for error reporting, so the flag demonstrates the
producer-driven pipeline, not the memory win -- procedural producers that
never materialize A are in ``benchmarks/strong_scaling.py``.

    PYTHONPATH=src python examples/meliso_solver.py            # 8 host devices
    PYTHONPATH=src python examples/meliso_solver.py --n 2048 --tol 1e-3
    PYTHONPATH=src python examples/meliso_solver.py --mesh 4,2 --producer
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
    ap.add_argument("--n", type=int, default=4096)
    ap.add_argument("--tol", type=float, default=1e-3,
                    help="relative-residual stopping tolerance")
    ap.add_argument("--maxiter", type=int, default=50)
    # epiram (64 levels) by default: the 8-level devices' quantization noise
    # floor caps the corrected solve around ~5e-3 relative error, while the
    # precision device reaches <= 1e-3 (sweep the rest via --device /
    # benchmarks/solver_convergence.py).
    ap.add_argument("--device", default="epiram")
    ap.add_argument("--cell", type=int, default=256)
    ap.add_argument("--no-ec", action="store_true")
    ap.add_argument("--mesh", default="2,4", metavar="R,C",
                    help="mesh shape: R row shards x C contraction shards")
    ap.add_argument("--producer", action="store_true",
                    help="exercise the producer-driven distributed code path "
                         "(here the producer reads a dense copy kept for "
                         "error reporting, so it demonstrates the pipeline, "
                         "not the memory win; see "
                         "benchmarks/strong_scaling.py for procedural "
                         "producers that never materialize A)")
    args = ap.parse_args()

    try:
        rows, cols = (int(v) for v in args.mesh.split(","))
    except ValueError:
        raise SystemExit(f"--mesh must be 'R,C' integers, got {args.mesh!r}")
    if rows * cols > jax.device_count():
        raise SystemExit(
            f"--mesh {rows}x{cols} needs {rows * cols} devices but only "
            f"{jax.device_count()} are available")
    mesh = make_mesh((rows, cols), ("data", "model"))
    n = args.n
    key = jax.random.PRNGKey(0)
    # Diagonally-dominant SPD system (spectrum ~2 +- O(1/sqrt(n))).
    r = jax.random.normal(key, (n, n), jnp.float32) / n
    a = r + r.T + 2.0 * jnp.eye(n, dtype=jnp.float32)
    x_true = jax.random.normal(jax.random.fold_in(key, 1), (n,), jnp.float32)
    b = a @ x_true

    local = (n // rows, n // cols)
    geom = MCAGeometry(tile_rows=max(local[0] // args.cell, 1),
                       tile_cols=max(local[1] // args.cell, 1),
                       cell_rows=args.cell, cell_cols=args.cell)
    cfg = CrossbarConfig(device=get_device(args.device), geom=geom,
                         k_iters=5, ec=not args.no_ec)

    engine = AnalogEngine(cfg, execution="distributed", mesh=mesh)
    if args.producer:
        cap_m, cap_n = cfg.geom.capacity
        mb, nb = -(-n // cap_m), -(-n // cap_n)
        a_pad = jnp.pad(a, ((0, mb * cap_m - n), (0, nb * cap_n - n)))
        blocks = a_pad.reshape(mb, cap_m, nb, cap_n).transpose(0, 2, 1, 3)
        A = engine.program(lambda i, j: blocks[i, j], key,
                           shape=(n, n))       # programmed ONCE, per window
    else:
        A = engine.program(a, key)             # programmed ONCE
    print(f"n={n} device={args.device} ec={not args.no_ec} "
          f"producer={args.producer} "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}")
    print(f"one-time write energy (mean/MCA-system) = "
          f"{float(A.write_stats.energy_j):.3e} J, "
          f"latency = {float(A.write_stats.latency_s):.4f} s\n")

    # The analog noise floor of ONE corrected MVM: solves cannot reliably
    # push their true residual below the operator's own relative error, so a
    # tighter --tol than this is unreachable on this device/EC configuration.
    y_probe = A @ x_true
    noise_floor = float(rel_l2(y_probe, b))
    below_floor = args.tol < noise_floor
    if below_floor:
        print(f"WARNING: --tol {args.tol:.1e} is below the analog noise "
              f"floor ~{noise_floor:.1e} of this configuration; solvers will "
              "stall at the floor (use repro.solvers.refine to converge "
              "below it).  Reporting achieved residuals instead of "
              "asserting convergence.\n")

    runs = [
        ("richardson omega=1/3 (old loop)",
         lambda: solvers.richardson(A, b, omega=1.0 / 3.0, tol=args.tol,
                                    maxiter=args.maxiter)),
        ("richardson auto-omega",
         lambda: solvers.richardson(A, b, tol=args.tol,
                                    maxiter=args.maxiter)),
        ("cg",
         lambda: solvers.cg(A, b, tol=args.tol, maxiter=args.maxiter)),
    ]
    # The convergence asserts hold for the default precision configuration;
    # the noisy 8-level devices / --no-ec runs are demonstrations of the
    # quantization floor, and a below-floor --tol is physically unreachable
    # (warned above) -- neither is expected to hit --tol.
    check = args.device == "epiram" and not args.no_ec and not below_floor
    print(f"{'solver':34s} {'iters':>5s} {'resid':>9s} {'x err':>9s} "
          f"{'E_write J':>10s} {'E_iters J':>10s}")
    baseline_iters = None
    for name, run in runs:
        res = run()
        err = float(rel_l2(res.x, x_true))
        led = res.ledger
        print(f"{name:34s} {res.iterations:5d} {res.final_residual:9.2e} "
              f"{err:9.2e} {led.write_energy_j:10.3e} "
              f"{led.iteration_energy_j:10.3e}")
        if baseline_iters is None:
            baseline_iters = res.iterations
        elif check:
            assert res.iterations < baseline_iters, \
                (name, res.iterations, baseline_iters)
            assert err <= args.tol, (name, err)
        assert led.write_energy_j > 0 and led.iteration_energy_j > 0
    if below_floor:
        print(f"\nnoise floor ~{noise_floor:.1e} (requested tol "
              f"{args.tol:.1e} not reachable without refinement)")

    print("\nper-MVM input-write energy = "
          f"{float(A.input_write_stats(batch=1).energy_j):.3e} J "
          "(amortized against one programmed image)")


if __name__ == "__main__":
    main()
