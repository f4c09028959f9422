"""Multi-exposure 1-D deblurring on ONE programmed crossbar image (LSQR).

A classic rectangular inverse problem: a piecewise-smooth signal is
observed through TWO Gaussian blur kernels of different widths (stacked
into an overdetermined (2n, n) operator) with additive readout noise, and
recovered by ``min ||A x - b||``.  Both Golub-Kahan directions -- ``A @ v``
and ``A.T @ u`` -- read the SAME conductance image: the operator is
programmed exactly once and every bidiagonalization step (one corrected
forward MVM + one corrected TRANSPOSED MVM) amortizes that write, with
forward and transposed input-write costs billed separately in the
:class:`~repro.solvers.SolveLedger`.

The example solves with both :func:`repro.solvers.lsqr` and
:func:`repro.solvers.lsmr` (same bidiagonalization, different recurrence:
LSMR monotonically decreases ``||A^T r||``).  Blur operators are
ill-conditioned, so iteration count acts as regularization
(semiconvergence) and the dense SVD solution would amplify the noise --
the oracle here is the same algorithm on the exact digital operator at
the same tolerance, compared in OBSERVATION space (``A x``, which the
data constrain) and by reconstruction error against the known truth.

    PYTHONPATH=src python examples/meliso_lstsq.py
    PYTHONPATH=src python examples/meliso_lstsq.py --n 256 --sigma 4.0
    PYTHONPATH=src python examples/meliso_lstsq.py --device taox-hfox
"""
import argparse

import jax
import jax.numpy as jnp

from repro import solvers
from repro.core import CrossbarConfig, MCAGeometry, get_device, rel_l2
from repro.engine import AnalogEngine
from repro.launch.compile_cache import enable_compile_cache


def blur_matrix(n: int, sigma: float) -> jnp.ndarray:
    """(n, n) circulant Gaussian blur with kernel width ``sigma``."""
    idx = jnp.arange(n, dtype=jnp.float32)
    d = jnp.minimum(jnp.abs(idx[:, None] - idx[None, :]),
                    n - jnp.abs(idx[:, None] - idx[None, :]))
    k = jnp.exp(-0.5 * (d / sigma) ** 2)
    return k / jnp.sum(k, axis=1, keepdims=True)


def piecewise_signal(n: int, key) -> jnp.ndarray:
    """A few random steps + a smooth bump: edges AND gradients to recover."""
    k1, k2 = jax.random.split(key)
    steps = jnp.cumsum(jnp.where(
        jax.random.uniform(k1, (n,)) < 4.0 / n,
        jax.random.normal(k2, (n,)), 0.0))
    t = jnp.linspace(0.0, 1.0, n)
    bump = 0.8 * jnp.exp(-0.5 * ((t - 0.35) / 0.08) ** 2)
    return steps + bump


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=128, help="signal length")
    ap.add_argument("--sigma", type=float, default=2.0,
                    help="width of the narrower blur kernel")
    ap.add_argument("--noise", type=float, default=1e-3,
                    help="additive observation noise level")
    ap.add_argument("--tol", type=float, default=1e-5)
    ap.add_argument("--maxiter", type=int, default=200)
    ap.add_argument("--device", default="epiram")
    ap.add_argument("--cell", type=int, default=32)
    args = ap.parse_args()

    key = jax.random.PRNGKey(0)
    kx, kn, kp = jax.random.split(key, 3)
    x_true = piecewise_signal(args.n, kx)
    # Two exposures through different blurs -> overdetermined (2n, n).
    a = jnp.concatenate([blur_matrix(args.n, args.sigma),
                         blur_matrix(args.n, 2.0 * args.sigma)], axis=0)
    b = a @ x_true + args.noise * jax.random.normal(kn, (2 * args.n,))

    geom = MCAGeometry(tile_rows=1, tile_cols=1,
                       cell_rows=args.cell, cell_cols=args.cell)
    cfg = CrossbarConfig(device=get_device(args.device), geom=geom,
                         k_iters=5, ec=True)
    engine = AnalogEngine(cfg)
    A = engine.program(a, kp)

    print(f"deblurring: ({2 * args.n}, {args.n}) two-exposure operator, "
          f"device={args.device}, noise={args.noise:g}")
    print(f"one-time write energy = {float(A.write_stats.energy_j):.3e} J\n")

    runs = {}
    print(f"{'solver':16s} {'iters':>6s} {'residual':>9s} "
          f"{'vs truth':>9s} {'E_iters J':>10s}")
    for algo, fn in (("lsqr", solvers.lsqr), ("lsmr", solvers.lsmr)):
        digital = fn(a, b, tol=args.tol, maxiter=args.maxiter)
        analog = fn(A, b, tol=args.tol, maxiter=args.maxiter, key=kp)
        runs[algo] = (digital, analog)
        for tag, res in ((f"{algo} digital", digital),
                         (f"{algo} analog", analog)):
            print(f"{tag:16s} {res.iterations:6d} "
                  f"{res.final_residual:9.2e} "
                  f"{float(rel_l2(res.x, x_true)):9.2e} "
                  f"{res.ledger.iteration_energy_j:10.3e}")

    digital, analog = runs["lsqr"]
    assert digital.converged and analog.converged
    # Observation space is what the data constrain: both reconstructions
    # must predict the same (de)blurred measurements...
    obs_gap = float(rel_l2(a @ analog.x, a @ digital.x))
    assert obs_gap <= 1e-3, obs_gap
    # ...and the analog reconstruction must match the digital QUALITY.
    err_a = float(rel_l2(analog.x, x_true))
    err_d = float(rel_l2(digital.x, x_true))
    assert err_a <= 1.2 * err_d + 1e-3, (err_a, err_d)

    led = analog.ledger
    print(f"\nledger: {led.mvms} forward MVMs + {led.mvms_t} transposed "
          f"MVMs against one programmed image, write "
          f"{led.write_energy_j:.3e} J")
    print(f"analog LSQR predicts the digital observations to {obs_gap:.1e}; "
          f"truth error {err_a:.3f} vs digital {err_d:.3f}")


if __name__ == "__main__":
    main()
