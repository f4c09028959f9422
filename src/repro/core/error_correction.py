"""Two-tier error correction (the paper's core algorithmic contribution).

Tier 1 -- first-order cancellation (paper Eq. 4-7):
    given Ã = A(1+eps_A) and x̃ = x(1+eps_x),
        p = Ãx + Ax̃ - Ãx̃ = Ax(1 - eps_A eps_x)
    cancels every first-order term, leaving the second-order product only.

    Two execution modes are provided:
      * ``faithful``: the paper's three analog products (3 matmuls).
      * ``fused``:    p = Ã(x - x̃) + Ax̃  -- algebraically identical, 2 matmuls
                      (a beyond-paper 33% FLOP reduction; validated in tests).

Tier 2 -- second-order denoising (paper Eq. 8-10, Algorithm 5):
    y(lambda) = (I + lambda * L^T L)^{-1} p,   L = I + h * superdiag (h = -1).

    (I + lambda L^T L) is symmetric positive-definite *tridiagonal*, so three
    methods are provided (all validated against each other):
      * ``dense``:   the paper-faithful dense inverse (O(n^3) setup, O(n^2) apply)
      * ``thomas``:  exact Thomas-algorithm solve, O(n) sequential
      * ``neumann``: truncated Neumann series y ~= p - lambda*K p + (lambda*K)^2 p ...
                     For the paper's lambda = 1e-12 the first-order truncation error
                     is O(lambda^2) ~ 1e-24, far below float32 resolution -- this
                     turns the solve into a 3-point stencil (O(n), fully parallel,
                     fuseable into the matmul epilogue).  Beyond-paper optimization.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "first_order_correct",
    "build_l_matrix",
    "tridiag_coeffs",
    "denoise_least_square",
]


# --------------------------------------------------------------------------- #
# Tier 1: first-order error correction
# --------------------------------------------------------------------------- #

def first_order_correct(
    a: jnp.ndarray,
    a_tilde: jnp.ndarray,
    x: jnp.ndarray,
    x_tilde: jnp.ndarray,
    *,
    mode: str = "fused",
) -> jnp.ndarray:
    """p = Ãx + Ax̃ - Ãx̃ (paper Eq. 7). ``x`` may be a vector or a matrix of
    column vectors; matmul semantics follow ``a @ x``.
    """
    if mode == "faithful":
        # The paper's three analog products, combined digitally.
        return a_tilde @ x + a @ x_tilde - a_tilde @ x_tilde
    if mode == "fused":
        # Identical algebra, one fewer matmul: Ã(x - x̃) + Ax̃.
        return a_tilde @ (x - x_tilde) + a @ x_tilde
    raise ValueError(f"unknown first-order EC mode {mode!r}")


# --------------------------------------------------------------------------- #
# Tier 2: regularized least-squares denoising
# --------------------------------------------------------------------------- #

def build_l_matrix(n: int, h: float = -1.0, dtype=jnp.float32) -> jnp.ndarray:
    """First-order differential matrix L: 1 on diag, h on superdiag (Eq. 9)."""
    return jnp.eye(n, dtype=dtype) + h * jnp.eye(n, k=1, dtype=dtype)


def tridiag_coeffs(n: int, lam: float, h: float = -1.0, dtype=jnp.float32):
    """(sub, diag, super) diagonals of M = I + lam * L^T L.

    L^T L is tridiagonal: (L^T L)_{ii} = 1 + h^2 for i >= 1, and 1 for i = 0;
    (L^T L)_{i,i+1} = (L^T L)_{i+1,i} = h.
    """
    diag = jnp.full((n,), 1.0 + lam * (1.0 + h * h), dtype=dtype)
    diag = diag.at[0].set(1.0 + lam)
    off = jnp.full((n - 1,), lam * h, dtype=dtype)
    return off, diag, off


def _dense_inverse_apply(p: jnp.ndarray, lam: float, h: float) -> jnp.ndarray:
    n = p.shape[0]
    l = build_l_matrix(n, h, dtype=jnp.float32)
    m = jnp.eye(n, dtype=jnp.float32) + lam * (l.T @ l)
    # The paper encodes M^{-1} on the MCA and multiplies; we form the explicit
    # inverse to stay faithful to that dataflow.
    m_inv = jnp.linalg.inv(m)
    return (m_inv @ p.astype(jnp.float32)).astype(p.dtype)


def _thomas_solve(p: jnp.ndarray, lam: float, h: float) -> jnp.ndarray:
    """Exact O(n) tridiagonal solve (vectorized over trailing dims of p)."""
    n = p.shape[0]
    sub, diag, sup = tridiag_coeffs(n, lam, h)
    pf = p.astype(jnp.float32)
    flat = pf.reshape(n, -1)

    def fwd(carry, inp):
        c_prev, d_prev = carry
        b_i, a_i, c_i, d_i = inp
        denom = b_i - a_i * c_prev
        c_new = c_i / denom
        d_new = (d_i - a_i * d_prev) / denom
        return (c_new, d_new), (c_new, d_new)

    a_seq = jnp.concatenate([jnp.zeros((1,), jnp.float32), sub])
    c_seq = jnp.concatenate([sup, jnp.zeros((1,), jnp.float32)])
    zero_row = jnp.zeros((flat.shape[1],), jnp.float32)
    (_, _), (cp, dp) = jax.lax.scan(
        fwd, (jnp.zeros((), jnp.float32), zero_row), (diag, a_seq, c_seq, flat)
    )

    def bwd(carry, inp):
        x_next = carry
        cp_i, dp_i = inp
        x_i = dp_i - cp_i * x_next
        return x_i, x_i

    _, xs = jax.lax.scan(bwd, zero_row, (cp, dp), reverse=True)
    return xs.reshape(p.shape).astype(p.dtype)


def _neumann_apply(p: jnp.ndarray, lam: float, h: float, terms: int = 2) -> jnp.ndarray:
    """y = sum_k (-lam K)^k p with K = L^T L as a 3-point stencil (no matrices)."""
    pf = p.astype(jnp.float32)

    def k_apply(v):
        # (K v)_i = (1+h^2) v_i + h v_{i-1} + h v_{i+1}, boundary-corrected:
        # row 0 diag is 1 (not 1+h^2).
        up = jnp.roll(v, -1, axis=0).at[-1].set(0.0)    # v_{i+1}
        dn = jnp.roll(v, 1, axis=0).at[0].set(0.0)      # v_{i-1}
        out = (1.0 + h * h) * v + h * (up + dn)
        return out.at[0].add(-(h * h) * v[0])

    y = pf
    term = pf
    for _ in range(terms - 1):
        term = -lam * k_apply(term)
        y = y + term
    return y.astype(p.dtype)


def denoise_least_square(
    p: jnp.ndarray,
    lam: float = 1e-12,
    h: float = -1.0,
    method: str = "neumann",
) -> jnp.ndarray:
    """Paper Algorithm 5 (second-order EC). ``p`` is (n,) or (n, batch)."""
    with jax.named_scope("meliso.tier2"):
        if method == "dense":
            return _dense_inverse_apply(p, lam, h)
        if method == "thomas":
            return _thomas_solve(p, lam, h)
        if method == "neumann":
            return _neumann_apply(p, lam, h)
    raise ValueError(f"unknown denoise method {method!r}")
