"""Benchmark matrices (paper section 2.2-2.3, Supplementary A).

The SuiteSparse collection is not available offline, so we provide surrogates
with the *published* dimensions and condition numbers (Supplementary Table 2).
`bcsstk02`-like matrices are built as Q diag(lambda) Q^T with a log-spaced
spectrum hitting the target kappa; `Iperturb` is the paper's slightly perturbed
identity.  For the strong-scaling sizes (up to 65,025^2) an *implicit* banded
generator produces capacity-sized blocks on demand so the matrix never
materializes (fed to ``AnalogEngine(cfg, execution="streamed")``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "make_spd_with_condition",
    "make_iperturb",
    "PAPER_MATRICES",
    "paper_matrix",
    "ImplicitBandedMatrix",
]


def make_spd_with_condition(n: int, kappa: float, seed: int = 0,
                            norm2: float = 1.0) -> np.ndarray:
    """Symmetric positive-definite n x n with condition number ~= kappa."""
    rng = np.random.default_rng(seed)
    q, _ = np.linalg.qr(rng.standard_normal((n, n)))
    lam = np.geomspace(norm2 / kappa, norm2, n)
    return (q * lam) @ q.T


def make_iperturb(n: int, scale: float = 0.05, seed: int = 1) -> np.ndarray:
    """The paper's Iperturb: identity + small perturbation, kappa ~= 1.23."""
    rng = np.random.default_rng(seed)
    p = rng.standard_normal((n, n)) * scale / np.sqrt(n)
    a = np.eye(n) + 0.5 * (p + p.T)
    return a


# Supplementary Table 2: (dim, kappa, ||A||_2). Dubcova2's stats are not
# published ("*"); we reuse Dubcova1's conditioning as the surrogate target.
_PAPER_SPECS: Dict[str, Tuple[int, float, float]] = {
    "bcsstk02": (66, 4.324971e3, 1.822575e4),
    "wang2": (2903, 2.305543e4, 4.138078),
    "add32": (4960, 1.366769e2, 5.749318e-2),
    "c-38": (8127, 1.530683e4, 6.083484e2),
    "dubcova1": (16129, 9.971199, 4.796329),
    "helm3d01": (32226, 2.451897e5, 5.052177e-1),
    "dubcova2": (65025, 9.971199, 4.796329),
}
PAPER_MATRICES = dict(_PAPER_SPECS)


def paper_matrix(name: str, seed: int = 0) -> np.ndarray:
    """Materialize a surrogate of a published matrix (small/medium sizes)."""
    key = name.lower()
    if key == "iperturb":
        return make_iperturb(66)
    if key not in _PAPER_SPECS:
        raise KeyError(f"unknown paper matrix {name!r}")
    n, kappa, norm2 = _PAPER_SPECS[key]
    if n > 20000:
        raise ValueError(
            f"{name} ({n}^2) should not be materialized; use ImplicitBandedMatrix")
    return make_spd_with_condition(n, kappa, seed=seed, norm2=norm2)


@dataclasses.dataclass(frozen=True)
class ImplicitBandedMatrix:
    """Procedurally generated banded-plus-noise matrix for huge problems.

    A = diag_dominant band + seeded pseudo-random off-band texture, defined
    blockwise: ``block(i, j)`` returns the (cap_m x cap_n) block at block-index
    (i, j) without ever forming A.  Deterministic in (seed, i, j).

    ``block`` is a *traceable* producer in the engine's sense (pure jax
    function of the index scalars), so streamed programming and every
    streamed MVM against it fuse into single-dispatch ``lax.scan`` pipelines.
    """

    n: int
    cap_m: int
    cap_n: int
    seed: int = 0
    bandwidth: int = 8
    diag: float = 4.0

    def block(self, i: int, j: int) -> jnp.ndarray:
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(self.seed), i), j)
        blk = 0.05 * jax.random.normal(key, (self.cap_m, self.cap_n), jnp.float32)
        r0, c0 = i * self.cap_m, j * self.cap_n
        rows = r0 + jnp.arange(self.cap_m)[:, None]
        cols = c0 + jnp.arange(self.cap_n)[None, :]
        dist = jnp.abs(rows - cols)
        band = jnp.where(dist <= self.bandwidth,
                         1.0 / (1.0 + dist.astype(jnp.float32)), 0.0)
        blk = blk * (dist <= 3 * self.bandwidth) + band
        blk = blk + self.diag * (rows == cols)
        valid = (rows < self.n) & (cols < self.n)
        return jnp.where(valid, blk, 0.0)

    def matvec(self, x: jnp.ndarray) -> jnp.ndarray:
        """Exact blockwise ground-truth A @ x: float32 products at HIGHEST
        precision, one scanned dispatch over the block grid."""
        return _banded_apply(self, x, False)

    def rmatvec(self, y: jnp.ndarray) -> jnp.ndarray:
        """Exact blockwise ground-truth A.T @ y (the transposed-MVM oracle)."""
        return _banded_apply(self, y, True)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _banded_apply(mat: ImplicitBandedMatrix, v: jnp.ndarray,
                  transpose: bool) -> jnp.ndarray:
    """A @ v (or A.T @ v) from the producer's blocks, never forming A."""
    nb_m = -(-mat.n // mat.cap_m)
    nb_n = -(-mat.n // mat.cap_n)
    nb_in, cap_in = (nb_m, mat.cap_m) if transpose else (nb_n, mat.cap_n)
    nb_out = nb_n if transpose else nb_m
    chunks = jnp.pad(v, (0, nb_in * cap_in - mat.n)).reshape(nb_in, cap_in)
    hi = jax.lax.Precision.HIGHEST

    def out_block(o):
        def step(acc, c):
            if transpose:
                prod = jnp.matmul(mat.block(c, o).T, chunks[c], precision=hi)
            else:
                prod = jnp.matmul(mat.block(o, c), chunks[c], precision=hi)
            return acc + prod, None

        cap_out = mat.cap_n if transpose else mat.cap_m
        acc, _ = jax.lax.scan(step, jnp.zeros((cap_out,), jnp.float32),
                              jnp.arange(nb_in))
        return acc

    return jax.lax.map(out_block, jnp.arange(nb_out)).reshape(-1)[: mat.n]
