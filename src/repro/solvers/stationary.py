"""Stationary iterative methods: Richardson (with auto-``omega``) and Jacobi.

The MELISO+ workhorse loop is Richardson iteration

    x_{k+1} = x_k + omega * (b - A x_k)

against one programmed analog image -- one corrected MVM per iteration, zero
re-programming.  Instead of a hand-tuned ``omega`` (the old example hard-coded
1/3), :func:`spectral_bounds` estimates the extremal eigenvalues of an SPD
``A`` with matvec-only power iteration (a second, shifted pass recovers
``lambda_min`` from ``lambda_max``) and :func:`richardson` defaults to the
optimal relaxation ``omega* = 2 / (lambda_min + lambda_max)``, deflated 5% on
the top end to absorb estimation error and analog noise.

The whole solve -- spectral estimate, ``lax.while_loop`` with tolerance-based
early stopping, residual history -- traces into one jitted computation, for
``b`` of shape (n,) or (n, batch).  With ``backend="pallas"`` the residual +
relaxed-step update fuses into :func:`repro.kernels.solver_richardson_update`.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from .base import (LinearOperator, SolveResult, as_operator, col_norms,
                   init_history, jit_core, pack_result, solver_core, use_pallas)

__all__ = ["richardson", "jacobi", "spectral_bounds", "estimate_omega"]

_TINY = 1e-30


def _power_iterate(matvec, n: int, key: jax.Array, iters: int,
                   shift: Optional[jnp.ndarray] = None):
    """(unit iterate, dominant |eigenvalue|) of A (or shift*I - A) by power
    iteration.

    Matvec-only: runs unchanged against analog/digital operators; each step
    consumes a fresh fold of ``key`` for the analog DAC noise.  The final
    iterate is exposed (not just the eigenvalue) so Krylov refiners --
    :func:`repro.solvers.lanczos` -- can seed their basis from it.
    """
    v0 = jax.random.normal(jax.random.fold_in(key, 0), (n, 1), jnp.float32)
    v0 = v0 / jnp.maximum(col_norms(v0), _TINY)

    def body(i, carry):
        v, _ = carry
        w = matvec(v, jax.random.fold_in(key, 1 + i))
        if shift is not None:
            w = shift * v - w
        lam = col_norms(w)[0]
        return w / jnp.maximum(lam, _TINY), lam

    v, lam = jax.lax.fori_loop(0, iters, body, (v0, jnp.float32(0.0)))
    return v, lam


def _power_extreme(matvec, n: int, key: jax.Array, iters: int,
                   shift: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Dominant |eigenvalue| only; see :func:`_power_iterate`."""
    return _power_iterate(matvec, n, key, iters, shift=shift)[1]


def spectral_bounds(
    A, *, key: Optional[jax.Array] = None, iters: int = 16,
    method: str = "power",
) -> Tuple[float, float]:
    """(lambda_min, lambda_max) estimates for SPD ``A``, matvec-only.

    ``method="power"``: ``lambda_max`` by plain power iteration, then
    ``lambda_min`` by a second power iteration on the shifted operator
    ``lambda_max * I - A`` (whose dominant eigenvalue is
    ``lambda_max - lambda_min``); costs ``2 * iters`` MVMs.
    ``method="lanczos"``: both ends from ONE Krylov sweep of
    :func:`repro.solvers.lanczos` (``iters`` steps; typically sharper per
    MVM, since Lanczos converges superlinearly at the spectrum ends where
    the shifted power method crawls).
    """
    op = as_operator(A)
    key = jax.random.PRNGKey(0) if key is None else key
    if method == "lanczos":
        from .eigen import lanczos
        res = lanczos(op, tol=0.0, maxiter=max(int(iters), 2), key=key)
        return float(res.eigenvalues[0]), float(res.eigenvalues[1])
    if method != "power":
        raise ValueError(f"method must be 'power' or 'lanczos', got "
                         f"{method!r}")

    @jax.jit
    def core(key):
        lmax = _power_extreme(op.matvec, op.n, jax.random.fold_in(key, 101),
                              iters)
        mu = _power_extreme(op.matvec, op.n, jax.random.fold_in(key, 202),
                            iters, shift=lmax)
        return lmax, lmax - mu

    lmax, lmin = core(key)
    return float(lmin), float(lmax)


def estimate_omega(A, *, key: Optional[jax.Array] = None,
                   iters: int = 16, method: str = "power") -> float:
    """The auto relaxation factor :func:`richardson` uses when ``omega=None``;
    ``method="lanczos"`` swaps the power-iteration bounds for a Lanczos
    sweep (see :func:`spectral_bounds`)."""
    lmin, lmax = spectral_bounds(A, key=key, iters=iters, method=method)
    return float(2.0 / (1.05 * lmax + max(lmin, 0.0)))


@solver_core
def _stationary_core(op: LinearOperator, scale_fn, b, x0, key, omega,
                     tol: float, maxiter: int, use_pallas: bool,
                     power_iters: int):
    """Shared Richardson/Jacobi while_loop.  ``scale_fn(r)`` maps the raw
    residual to the update direction (identity / D^{-1} r)."""
    batch = b.shape[1]
    bn = jnp.maximum(col_norms(b), _TINY)

    if omega is None:
        pkey = jax.random.fold_in(key, 900_001)
        lmax = _power_extreme(op.matvec, op.n, jax.random.fold_in(pkey, 1),
                              power_iters)
        mu = _power_extreme(op.matvec, op.n, jax.random.fold_in(pkey, 2),
                            power_iters, shift=lmax)
        lmin = jnp.maximum(lmax - mu, 0.0)
        om = 2.0 / (1.05 * lmax + lmin)
        # Power iteration runs on a single column whatever the RHS batch;
        # billed separately at the batch-1 input-write rate (see SolveLedger).
        pi_mvms = jnp.int32(2 * power_iters)
    else:
        om = jnp.float32(omega)
        pi_mvms = jnp.int32(0)

    def cond(state):
        k, _x, _h, rel, _m = state
        # NaN-robust: a NaN residual counts as not converged.
        return jnp.logical_and(k < maxiter,
                               jnp.logical_not(jnp.all(rel <= tol)))

    def body(state):
        k, x, hist, _rel, mvms = state
        y = op.matvec(x, jax.random.fold_in(key, k))
        if use_pallas and scale_fn is None:
            from repro.kernels import solver_richardson_update
            x_new, r = solver_richardson_update(x, b, y, om)
        else:
            r = b - y
            step = r if scale_fn is None else scale_fn(r)
            x_new = x + om * step
        rel = col_norms(r) / bn
        hist = hist.at[k].set(rel)
        return k + 1, x_new, hist, rel, mvms + 1

    state0 = (jnp.int32(0), x0, init_history(maxiter, batch),
              jnp.full((batch,), jnp.inf, jnp.float32), jnp.int32(0))
    k, x, hist, _rel, mvms = jax.lax.while_loop(cond, body, state0)
    return x, hist, k, mvms, pi_mvms


def richardson(
    A,
    b: jnp.ndarray,
    *,
    omega: Optional[float] = None,
    tol: float = 1e-6,
    maxiter: int = 200,
    x0: Optional[jnp.ndarray] = None,
    key: Optional[jax.Array] = None,
    power_iters: int = 16,
    backend: Optional[str] = None,
) -> SolveResult:
    """Richardson iteration ``x += omega * (b - A x)``, matvec-only.

    ``omega=None`` (the default) spends ``2 * power_iters`` extra MVMs on a
    power-iteration spectral estimate and uses the optimal SPD relaxation
    ``2 / (lambda_min + lambda_max)`` (top deflated 5%); those MVMs are
    charged to the ledger.  ``backend="pallas"`` fuses the update step.
    """
    op = as_operator(A)
    pallas = use_pallas(backend)
    squeeze = b.ndim == 1
    bb = (b[:, None] if squeeze else b).astype(jnp.float32)
    x0b = jnp.zeros_like(bb) if x0 is None else \
        (x0[:, None] if squeeze else x0).astype(jnp.float32)
    key = jax.random.PRNGKey(0) if key is None else key

    core = jit_core(op, lambda o: functools.partial(
        _stationary_core, o, None, tol=tol, maxiter=maxiter,
        use_pallas=pallas, power_iters=power_iters, omega=omega))
    x, hist, k, mvms, pi_mvms = core(bb, x0b, key)
    return pack_result(op, "richardson", x, hist, k, mvms, tol, squeeze,
                       mvms_single=pi_mvms)


def jacobi(
    A,
    b: jnp.ndarray,
    *,
    diag: Optional[jnp.ndarray] = None,
    omega: float = 1.0,
    tol: float = 1e-6,
    maxiter: int = 200,
    x0: Optional[jnp.ndarray] = None,
    key: Optional[jax.Array] = None,
) -> SolveResult:
    """(Weighted) Jacobi ``x += omega * D^{-1} (b - A x)``.

    The diagonal is digital metadata: taken from ``diag`` if given, else
    reconstructed from the programmed operands (``A_tilde + dA``) -- the
    analog array itself is only ever touched through MVMs.
    """
    op = as_operator(A)
    if diag is None:
        if op.dense is None:
            raise ValueError("jacobi needs diag= for a bare matvec operator")
        diag = jnp.diagonal(op.dense())
    dinv = (1.0 / jnp.asarray(diag, jnp.float32))[:, None]

    squeeze = b.ndim == 1
    bb = (b[:, None] if squeeze else b).astype(jnp.float32)
    x0b = jnp.zeros_like(bb) if x0 is None else \
        (x0[:, None] if squeeze else x0).astype(jnp.float32)
    key = jax.random.PRNGKey(0) if key is None else key

    core = jit_core(op, lambda o: functools.partial(
        _stationary_core, o, lambda r: dinv * r, tol=tol, maxiter=maxiter,
        use_pallas=False, power_iters=0, omega=omega))
    x, hist, k, mvms, _pi = core(bb, x0b, key)
    return pack_result(op, "jacobi", x, hist, k, mvms, tol, squeeze)
