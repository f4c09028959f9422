"""Least-squares solvers: LSQR and LSMR on the matvec+rmatvec operator.

The tile geometry has always supported non-square crossbars, and PR 5's
transposed corrected MVM (``rmatvec``) supplies exactly the two products
Golub-Kahan bidiagonalization consumes -- so overdetermined systems

    min_x || A x - b ||_2,        A (m, n) rectangular

run against ONE programmed rectangular image at one corrected ``A @ v`` plus
one corrected ``A.T @ u`` per iteration, the same per-iteration budget as
:mod:`~repro.solvers.pdhg` (and the regime of the companion RRAM-PDHG
paper).  Both methods are transcribed from the Paige-Saunders / Fong-Saunders
recurrences:

  * :func:`lsqr` -- CG on the normal equations ``A'A x = A'b`` in exact
    arithmetic, but built on the bidiagonalization so it never forms (or
    squares the conditioning of) ``A'A``;
  * :func:`lsmr` -- MINRES on the normal equations: the normal-equations
    residual ``||A'r_k||`` decreases MONOTONICALLY, which is the better
    behaved choice when analog noise makes late LSQR iterates fluctuate.

Residual semantics: least-squares solves of inconsistent systems do NOT
drive ``||b - A x||`` to zero, so the recorded per-iteration history (and
``SolveResult.final_residual``) is the *normal-equations* relative residual

    || A' (b - A x_k) ||  /  || A' b ||

which converges to zero for consistent AND inconsistent problems (the
optimality condition of least squares is ``A'r = 0``).  Both methods carry
this quantity for free from the rotation recurrences (``phibar * alpha * c``
for LSQR, ``|zetabar|`` for LSMR); the solver-contract suite recomputes it
digitally from the returned ``x``.

Everything else matches the house style: per-column multi-RHS panels,
NaN-robust ``lax.while_loop`` early stopping, the whole solve (init MVMs
included) one jitted program, forward and transposed MVMs billed separately
to the :class:`~repro.solvers.base.SolveLedger`, and unchanged operation
across ``local`` / ``streamed`` / ``distributed`` execution (including
``resident=False`` producers, where a 65,536^2 least-squares solve runs
with no A-sized array anywhere -- pinned by the invariant gate).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .base import (LinearOperator, SolveResult, as_operator, col_norms,
                   init_history, jit_core, pack_result)

__all__ = ["lsqr", "lsmr", "lsqr_pipeline", "lsmr_pipeline"]

_TINY = 1e-30


def _normalize(v):
    """(v / ||v||, ||v||) per column, guarded against zero columns."""
    nrm = col_norms(v)
    return v / jnp.maximum(nrm, _TINY)[None, :], nrm


def _unconverged(rel, tol):
    """NaN-robust: a NaN residual (breakdown) counts as not converged."""
    return jnp.logical_not(jnp.all(rel <= tol))


def _bidiag_init(op: LinearOperator, b, x0, key):
    """Shared Golub-Kahan start: u1 = r0/beta1, v1 = A'u1/alpha1.

    Consumes one forward MVM (the init residual ``b - A x0``) and one
    transposed MVM; ``alpha1 * beta1`` is ``||A'r0||``, the normal-equations
    residual at entry.
    """
    r0 = b - op.matvec(x0, jax.random.fold_in(key, 0))
    u, beta = _normalize(r0)
    v, alpha = _normalize(op.rmatvec(u, jax.random.fold_in(key, 1)))
    return u, v, alpha, beta


def _atb_norm(op: LinearOperator, b, key, alpha, beta, explicit_x0: bool):
    """||A'b|| -- the denominator of the recorded relative residual.

    With the default zero ``x0`` this is exactly ``alpha1 * beta1`` from the
    bidiagonalization start (``r0 = b``), costing nothing.  With a caller
    ``x0`` the start vector is ``b - A x0``, so one extra transposed
    full-panel MVM recovers the true normalization (billed by the wrapper).
    """
    if not explicit_x0:
        return jnp.maximum(alpha * beta, _TINY)
    atb = op.rmatvec(b, jax.random.fold_in(key, 900_011))
    return jnp.maximum(col_norms(atb), _TINY)


def _bidiag_step(op, u, v, alpha, key, k):
    """One Golub-Kahan continuation: new (u, beta, v, alpha).

    ``beta_{k+1} u_{k+1} = A v_k - alpha_k u_k`` (forward MVM, fold 2+2k),
    ``alpha_{k+1} v_{k+1} = A' u_{k+1} - beta_{k+1} v_k`` (transposed,
    fold 3+2k).  Folds continue the 0/1 init so every analog dispatch in the
    solve sees a distinct key.
    """
    u, beta = _normalize(
        op.matvec(v, jax.random.fold_in(key, 2 + 2 * k)) - alpha[None, :] * u)
    v, alpha = _normalize(
        op.rmatvec(u, jax.random.fold_in(key, 3 + 2 * k)) - beta[None, :] * v)
    return u, beta, v, alpha


# --------------------------------------------------------------------------- #
# LSQR (Paige & Saunders 1982)
# --------------------------------------------------------------------------- #

def _lsqr_core(op: LinearOperator, b, x0, key, *, tol: float, maxiter: int,
               explicit_x0: bool):
    batch = b.shape[1]
    u, v, alpha, beta = _bidiag_init(op, b, x0, key)
    atb = _atb_norm(op, b, key, alpha, beta, explicit_x0)
    rel0 = alpha * beta / atb

    def cond(state):
        k = state[0]
        rel = state[9]
        return jnp.logical_and(k < maxiter, _unconverged(rel, tol))

    def body(state):
        k, x, u, v, w, alpha, rhobar, phibar, hist, _rel, mvms = state
        u, beta, v, alpha = _bidiag_step(op, u, v, alpha, key, k)
        # Givens rotation eliminating beta from the lower bidiagonal.
        rho = jnp.maximum(
            jnp.sqrt(jnp.square(rhobar) + jnp.square(beta)), _TINY)
        c = rhobar / rho
        s = beta / rho
        theta = s * alpha
        rhobar = -c * alpha
        phi = c * phibar
        phibar = s * phibar
        x = x + (phi / rho)[None, :] * w
        w = v - (theta / rho)[None, :] * w
        # ||A'r_k|| = phibar_{k+1} * alpha_{k+1} * |c_k| (Paige-Saunders).
        rel = jnp.abs(phibar * alpha * c) / atb
        hist = hist.at[k].set(rel)
        return k + 1, x, u, v, w, alpha, rhobar, phibar, hist, rel, mvms + 1

    hist0 = init_history(maxiter, batch)
    state0 = (jnp.int32(0), x0, u, v, v, alpha, alpha, beta, hist0, rel0,
              jnp.int32(1))
    out = jax.lax.while_loop(cond, body, state0)
    k, x, hist, mvms = out[0], out[1], out[8], out[10]
    return x, hist, k, mvms, rel0


def lsqr_pipeline(
    op: LinearOperator,
    *,
    tol: float = 1e-4,
    maxiter: int = 200,
    explicit_x0: bool = False,
):
    """The jit-able LSQR core ``(b, x0, key) -> (x, hist, k, mvms, rel0)``.

    Exposed (like :func:`~repro.solvers.cg_pipeline`) so jaxpr-level tooling
    -- :mod:`repro.analysis.pipelines`, the invariant gate -- can trace the
    exact computation a least-squares solve dispatches.  ``b`` is an
    (m, batch) panel, ``x0`` (n, batch).  ``explicit_x0`` is the
    python-static switch for a caller-supplied start point (adds the one
    ``||A'b||`` normalization rmatvec).
    """
    return functools.partial(_lsqr_core, op, tol=tol, maxiter=maxiter,
                             explicit_x0=explicit_x0)


# --------------------------------------------------------------------------- #
# LSMR (Fong & Saunders 2011)
# --------------------------------------------------------------------------- #

def _lsmr_core(op: LinearOperator, b, x0, key, *, tol: float, maxiter: int,
               explicit_x0: bool):
    batch = b.shape[1]
    u, v, alpha, beta = _bidiag_init(op, b, x0, key)
    atb = _atb_norm(op, b, key, alpha, beta, explicit_x0)
    rel0 = alpha * beta / atb
    ones = jnp.ones((batch,), jnp.float32)
    zeros = jnp.zeros((batch,), jnp.float32)

    def cond(state):
        k = state[0]
        rel = state[14]
        return jnp.logical_and(k < maxiter, _unconverged(rel, tol))

    def body(state):
        (k, x, u, v, h, hbar, alpha, alphabar, zetabar, cbar, sbar, rho_old,
         rhobar_old, hist, _rel, mvms) = state
        u, beta, v, alpha = _bidiag_step(op, u, v, alpha, key, k)
        # First rotation: eliminate beta from the lower bidiagonal.
        rho = jnp.maximum(
            jnp.sqrt(jnp.square(alphabar) + jnp.square(beta)), _TINY)
        c = alphabar / rho
        s = beta / rho
        theta_new = s * alpha
        alphabar = c * alpha
        # Second rotation: the MINRES-style QR of the R factor.
        thetabar = sbar * rho
        rhotemp = cbar * rho
        rhobar = jnp.maximum(
            jnp.sqrt(jnp.square(rhotemp) + jnp.square(theta_new)), _TINY)
        cbar = rhotemp / rhobar
        sbar = theta_new / rhobar
        zeta = cbar * zetabar
        zetabar = -sbar * zetabar
        # Solution update through the two-level direction recurrences.
        hbar = h - (thetabar * rho
                    / jnp.maximum(rho_old * rhobar_old, _TINY))[None, :] * hbar
        x = x + (zeta / (rho * rhobar))[None, :] * hbar
        h = v - (theta_new / rho)[None, :] * h
        # ||A'r_k|| = |zetabar_{k+1}| -- monotone by construction.
        rel = jnp.abs(zetabar) / atb
        hist = hist.at[k].set(rel)
        return (k + 1, x, u, v, h, hbar, alpha, alphabar, zetabar, cbar, sbar,
                rho, rhobar, hist, rel, mvms + 1)

    hist0 = init_history(maxiter, batch)
    state0 = (jnp.int32(0), x0, u, v, v, jnp.zeros_like(x0), alpha, alpha,
              alpha * beta, ones, zeros, ones, ones, hist0, rel0,
              jnp.int32(1))
    out = jax.lax.while_loop(cond, body, state0)
    k, x, hist, mvms = out[0], out[1], out[13], out[15]
    return x, hist, k, mvms, rel0


def lsmr_pipeline(
    op: LinearOperator,
    *,
    tol: float = 1e-4,
    maxiter: int = 200,
    explicit_x0: bool = False,
):
    """The jit-able LSMR core ``(b, x0, key) -> (x, hist, k, mvms, rel0)``;
    see :func:`lsqr_pipeline` for the calling convention."""
    return functools.partial(_lsmr_core, op, tol=tol, maxiter=maxiter,
                             explicit_x0=explicit_x0)


# --------------------------------------------------------------------------- #
# Wrappers
# --------------------------------------------------------------------------- #

def _lstsq_solve(core_fn, name: str, A, b, *, tol, maxiter, x0, key):
    op = as_operator(A)
    if op.rmatvec is None:
        raise ValueError(
            f"{name} needs an operator with rmatvec (A.T @ u): pass an "
            "AnalogMatrix / dense array, or as_operator(mv, shape=..., "
            "rmatvec=...)")
    m, n = op.shape
    squeeze = b.ndim == 1
    bb = (b[:, None] if squeeze else b).astype(jnp.float32)
    if bb.shape[0] != m:
        raise ValueError(
            f"b has {bb.shape[0]} rows for an operator of shape {op.shape}; "
            f"expected ({m}, batch)")
    explicit_x0 = x0 is not None
    x0b = jnp.zeros((n, bb.shape[1]), jnp.float32) if x0 is None else \
        (x0[:, None] if squeeze else x0).astype(jnp.float32)
    key = jax.random.PRNGKey(0) if key is None else key
    core = jit_core(op, lambda o: core_fn(o, tol=tol, maxiter=maxiter,
                                          explicit_x0=explicit_x0))
    x, hist, k, mvms, rel0 = core(bb, x0b, key)
    # Forward MVMs: init + one per iteration; transposed MVMs mirror them
    # exactly, plus the full-panel ||A'b|| normalization when x0 was given.
    return pack_result(op, name, x, hist, k, mvms, tol, squeeze, rel0=rel0,
                       mvms_t=int(mvms) + (1 if explicit_x0 else 0))


def lsqr(
    A,
    b: jnp.ndarray,
    *,
    tol: float = 1e-4,
    maxiter: int = 200,
    x0: Optional[jnp.ndarray] = None,
    key: Optional[jax.Array] = None,
) -> SolveResult:
    """LSQR for ``min ||A x - b||`` on rectangular ``A``; one corrected
    matvec + one corrected rmatvec per iteration.

    ``b`` is (m,) / (m, batch); each column is an independent least-squares
    problem.  The residual history and convergence test use the
    normal-equations relative residual ``||A'(b - A x)|| / ||A'b||`` (zero
    at optimality for consistent AND inconsistent systems).  Returns a
    :class:`~repro.solvers.base.SolveResult` whose ledger bills forward and
    transposed MVMs separately against the one-time image write.
    """
    return _lstsq_solve(lsqr_pipeline, "lsqr", A, b, tol=tol,
                        maxiter=maxiter, x0=x0, key=key)


def lsmr(
    A,
    b: jnp.ndarray,
    *,
    tol: float = 1e-4,
    maxiter: int = 200,
    x0: Optional[jnp.ndarray] = None,
    key: Optional[jax.Array] = None,
) -> SolveResult:
    """LSMR for ``min ||A x - b||``: MINRES on the normal equations, so
    ``||A'r||`` decreases monotonically -- the stabler pick when analog
    noise makes late LSQR iterates fluctuate.  Same contract as
    :func:`lsqr`."""
    return _lstsq_solve(lsmr_pipeline, "lsmr", A, b, tol=tol,
                        maxiter=maxiter, x0=x0, key=key)
