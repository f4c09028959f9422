"""Spectral graph partitioning on ONE programmed crossbar image.

The graph Laplacian of a planted two-community graph (a stochastic block
model) is programmed ONCE into analog conductances, then interrogated
purely through corrected matvecs:

  * :func:`repro.solvers.lanczos` sweeps BOTH extremal eigenpairs in one
    pass -- ``lambda_max`` bounds the spectrum (step sizing), and the
    near-zero ``lambda_min`` certifies the Laplacian's constant kernel;
  * :func:`repro.solvers.lobpcg` (``which="smallest"``, k=2) extracts the
    Fiedler pair -- the second-smallest eigenvector -- whose SIGN pattern
    is the spectral bipartition.

The planted labels are known, so the example reports partition accuracy
(up to the global sign flip) for the analog solve against the digital
oracle, plus the write-once/iterate-many energy split.

    PYTHONPATH=src python examples/meliso_spectral.py
    PYTHONPATH=src python examples/meliso_spectral.py --n 256 --p-out 0.05
    PYTHONPATH=src python examples/meliso_spectral.py --device taox-hfox
"""
import argparse

import jax
import jax.numpy as jnp

from repro import solvers
from repro.core import CrossbarConfig, MCAGeometry, get_device
from repro.engine import AnalogEngine
from repro.launch.compile_cache import enable_compile_cache


def sbm_laplacian(n: int, p_in: float, p_out: float, key):
    """Laplacian of a two-block stochastic block model + planted labels."""
    half = n // 2
    labels = jnp.concatenate([jnp.ones((half,)), -jnp.ones((n - half,))])
    same = labels[:, None] == labels[None, :]
    p = jnp.where(same, p_in, p_out)
    u = jax.random.uniform(key, (n, n))
    upper = jnp.triu(jnp.where(u < p, 1.0, 0.0), k=1)
    adj = upper + upper.T
    lap = jnp.diag(jnp.sum(adj, axis=1)) - adj
    return lap.astype(jnp.float32), labels


def accuracy(fiedler, labels) -> float:
    """Fraction of planted labels recovered, up to the global sign flip."""
    pred = jnp.where(fiedler >= 0, 1.0, -1.0)
    hits = float(jnp.mean(pred == labels))
    return max(hits, 1.0 - hits)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=128, help="graph vertices")
    ap.add_argument("--p-in", type=float, default=0.30,
                    help="intra-community edge probability")
    ap.add_argument("--p-out", type=float, default=0.02,
                    help="inter-community edge probability")
    ap.add_argument("--tol", type=float, default=1e-4)
    ap.add_argument("--maxiter", type=int, default=100)
    ap.add_argument("--device", default="epiram")
    ap.add_argument("--cell", type=int, default=32)
    args = ap.parse_args()

    key = jax.random.PRNGKey(0)
    kg, kp = jax.random.split(key)
    lap, labels = sbm_laplacian(args.n, args.p_in, args.p_out, kg)

    geom = MCAGeometry(tile_rows=1, tile_cols=1,
                       cell_rows=args.cell, cell_cols=args.cell)
    cfg = CrossbarConfig(device=get_device(args.device), geom=geom,
                         k_iters=5, ec=True)
    engine = AnalogEngine(cfg)
    L = engine.program(lap, kp)

    print(f"SBM: {args.n} vertices, p_in={args.p_in}, p_out={args.p_out}, "
          f"device={args.device}")
    print(f"one-time write energy = {float(L.write_stats.energy_j):.3e} J\n")

    # One Lanczos sweep brackets the whole spectrum, matvec-only.
    sweep = solvers.lanczos(L, tol=args.tol, maxiter=48, key=kp)
    lmin, lmax = (float(v) for v in sweep.eigenvalues)
    ref = jnp.linalg.eigvalsh(lap)
    print(f"lanczos spectrum: [{lmin:.4f}, {lmax:.4f}] in "
          f"{sweep.iterations} steps (digital eigh: [{float(ref[0]):.4f}, "
          f"{float(ref[-1]):.4f}])")

    digital = solvers.lobpcg(lap, 2, which="smallest", tol=args.tol,
                             maxiter=args.maxiter)
    analog = solvers.lobpcg(L, 2, which="smallest", tol=args.tol,
                            maxiter=args.maxiter, key=kp)

    print(f"\n{'solver':16s} {'iters':>6s} {'ritz res':>9s} "
          f"{'lambda_2':>9s} {'accuracy':>9s} {'E_iters J':>10s}")
    for tag, res in (("lobpcg digital", digital), ("lobpcg analog", analog)):
        acc = accuracy(res.x[:, 1], labels)
        print(f"{tag:16s} {res.iterations:6d} {res.final_residual:9.2e} "
              f"{float(res.eigenvalues[1]):9.4f} {acc:9.3f} "
              f"{res.ledger.iteration_energy_j:10.3e}")

    # The Laplacian kernel is the constant vector: lambda_min ~ 0.
    assert abs(lmin) <= 1e-2 * max(1.0, lmax), (lmin, lmax)
    assert lmax <= 1.05 * float(ref[-1]) + args.tol
    acc_a = accuracy(analog.x[:, 1], labels)
    acc_d = accuracy(digital.x[:, 1], labels)
    assert acc_d >= 0.95, acc_d
    assert acc_a >= acc_d - 0.05, (acc_a, acc_d)

    led = analog.ledger
    print(f"\nledger: {led.mvms + led.mvms_single} matvecs against one "
          f"programmed image, write {led.write_energy_j:.3e} J")
    print(f"analog Fiedler partition recovers {100 * acc_a:.1f}% of the "
          f"planted communities (digital: {100 * acc_d:.1f}%)")


if __name__ == "__main__":
    main()
