"""Extremal eigenpair solvers: Lanczos and LOBPCG on the analog operator.

Eigen-solves are the purest expression of the paper's amortization thesis:
the iteration touches ``A`` ONLY through matvecs against the one programmed
image, and what comes back (extremal eigenvalues / singular values) feeds
straight back into the step-size machinery of the other solvers --
:func:`repro.solvers.richardson`'s relaxation ``2/(lmin+lmax)``,
:func:`repro.solvers.pdhg`'s ``tau = sigma = eta/||A||_2``.  Two methods:

  * :func:`lanczos` -- both extremal eigenpairs of a SYMMETRIC operator from
    one Krylov sweep.  The basis is seeded from the same power-iteration
    estimator :mod:`repro.solvers.stationary` uses (the power iterate is
    already rich in the dominant eigenvector, so Lanczos converges in fewer
    analog MVMs than a cold random start), fully reorthogonalized (float32 +
    analog noise make the textbook three-term recurrence lose orthogonality
    fast), with Ritz pairs extracted per iteration from a masked fixed-shape
    tridiagonal -- the same masked-basis device-friendly pattern as
    ``_gmres_cycle``.
  * :func:`lobpcg` -- a block of ``k`` extremal eigenpairs; each iteration is
    ONE batched 3k-column matvec (the [X | R | P] search subspace in a single
    analog dispatch), which is exactly the regime where the engine's
    batched-input amortization pays.

Both record the per-iteration relative Ritz residual
``||A y - theta y|| / |theta|`` as the :class:`SolveResult` history (the
solver-contract suite recomputes it digitally from the returned pairs), bill
every analog MVM to the :class:`~repro.solvers.base.SolveLedger`, and run as
single jitted programs with NaN-robust ``lax.while_loop`` early stopping.

:func:`operator_norm` estimates ``||A||_2`` for RECTANGULAR operators by
running :func:`lanczos` on the symmetric augmentation ``[[0, A], [A', 0]]``
(extremal eigenvalue = extremal singular value; one matvec + one rmatvec per
Lanczos step) -- the drop-in upgrade for PDHG's power-iteration step sizing.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .base import (LinearOperator, SolveResult, as_operator, col_norms,
                   init_history, jit_core, pack_result, solver_core)
from .stationary import _power_iterate

__all__ = ["lanczos", "lobpcg", "operator_norm", "lanczos_pipeline",
           "lobpcg_pipeline"]

_TINY = 1e-30


def _unconverged(rel, tol):
    """NaN-robust: a NaN Ritz residual (breakdown) counts as not converged."""
    return jnp.logical_not(jnp.all(rel <= tol))


# --------------------------------------------------------------------------- #
# Lanczos
# --------------------------------------------------------------------------- #

@solver_core
def _lanczos_core(op: LinearOperator, key, *, tol: float, maxiter: int,
                  seed_iters: int):
    n = op.n
    m = maxiter
    # Seed from the power-iteration estimator (stationary.py): the iterate is
    # dominated by the top eigenvector, which Lanczos then refines while
    # simultaneously pulling out the bottom of the spectrum.
    v1, _ = _power_iterate(op.matvec, n, jax.random.fold_in(key, 900_007),
                           seed_iters)
    idx = jnp.arange(m)

    def cond(state):
        k = state[0]
        rel = state[10]
        return jnp.logical_and(k < maxiter, _unconverged(rel, tol))

    def body(state):
        (k, V, vk, v_prev, beta_prev, alphas, betas, Y, theta2, hist, _rel,
         mvms) = state
        w = op.matvec(vk, jax.random.fold_in(key, k))
        alpha = jnp.sum(vk * w)
        w = w - alpha * vk - beta_prev * v_prev
        # Full reorthogonalization against the stored basis; unfilled columns
        # of V are zero, so the masked projection is just V (V' w).
        w = w - V @ (V.T @ w)
        beta = col_norms(w)[0]
        alphas = alphas.at[k].set(alpha)
        betas = betas.at[k].set(beta)
        V = V.at[:, k].set(vk[:, 0])
        # Fixed-shape masked tridiagonal: the active (k+1)-block of T, padded
        # on the diagonal with the mean of the seen alphas.  The pad block is
        # decoupled (its off-diagonals are masked to zero) and the mean of a
        # symmetric matrix's diagonal lies inside its spectrum, so the padded
        # eigenvalues sit strictly between the true extremal Ritz values.
        pad = jnp.sum(alphas) / (k + 1)
        diag = jnp.where(idx <= k, alphas, pad)
        off = jnp.where(idx[:-1] < k, betas[:-1], 0.0)
        t_mat = jnp.diag(diag) + jnp.diag(off, 1) + jnp.diag(off, -1)
        theta, s_mat = jnp.linalg.eigh(t_mat)
        s_pair = jnp.stack([s_mat[:, 0], s_mat[:, -1]], axis=1)  # (m, 2)
        theta2 = jnp.stack([theta[0], theta[-1]])
        # Ritz residual ||A y - theta y|| = |beta_k * s[k]| (last active row).
        resid = jnp.abs(beta * s_pair[k, :])
        rel = resid / jnp.maximum(jnp.abs(theta2), _TINY)
        # One Lanczos step cannot separate the spectrum ends; the k=0 Ritz
        # data is degenerate by construction, so never report it converged.
        rel = jnp.where(k < 1, jnp.full_like(rel, jnp.inf), rel)
        hist = hist.at[k].set(rel)
        Y = V @ s_pair
        v_next = w / jnp.maximum(beta, _TINY)
        return (k + 1, V, v_next, vk, beta, alphas, betas, Y, theta2, hist,
                rel, mvms + 1)

    zcol = jnp.zeros((n, 1), jnp.float32)
    state0 = (jnp.int32(0), jnp.zeros((n, m), jnp.float32), v1, zcol,
              jnp.float32(0.0), jnp.zeros((m,), jnp.float32),
              jnp.zeros((m,), jnp.float32), jnp.zeros((n, 2), jnp.float32),
              jnp.zeros((2,), jnp.float32), init_history(m, 2),
              jnp.full((2,), jnp.inf, jnp.float32), jnp.int32(seed_iters))
    out = jax.lax.while_loop(cond, body, state0)
    k, y_pair, theta2, hist, mvms = out[0], out[7], out[8], out[9], out[11]
    return y_pair, theta2, hist, k, mvms


def lanczos_pipeline(
    op: LinearOperator,
    *,
    tol: float = 1e-4,
    maxiter: int = 48,
    seed_iters: int = 8,
):
    """The jit-able Lanczos core ``(key) -> (Y, theta, hist, k, mvms)``.

    ``Y`` is the (n, 2) [bottom | top] Ritz-vector panel, ``theta`` the
    matching (2,) eigenvalue estimates.  Exposed for the invariant gate: the
    whole sweep -- power-iteration seeding, reorthogonalized recurrence,
    per-step tridiagonal Ritz extraction -- is one traced program.
    """
    return functools.partial(_lanczos_core, op, tol=tol, maxiter=maxiter,
                             seed_iters=seed_iters)


def lanczos(
    A,
    *,
    tol: float = 1e-4,
    maxiter: int = 48,
    seed_iters: int = 8,
    key: Optional[jax.Array] = None,
) -> SolveResult:
    """Both extremal eigenpairs of a symmetric operator, matvec-only.

    Returns a :class:`SolveResult` whose ``x`` is the (n, 2) panel of
    [lambda_min | lambda_max] eigenvectors, with the estimates themselves in
    ``result.eigenvalues`` (shape (2,), ascending).  The residual history is
    the relative Ritz residual ``||A y - theta y|| / |theta|`` per pair; all
    MVMs (the ``seed_iters`` power-iteration seed steps plus one per Lanczos
    step, every one batch-1) are billed at the batch-1 input rate.

    Feed the output back into step sizing:
    ``2.0 / (1.05 * lmax + lmin)`` is :func:`repro.solvers.richardson`'s
    relaxation (see ``estimate_omega(method="lanczos")``).
    """
    op = as_operator(A)
    m_, n_ = op.shape
    if m_ != n_:
        raise ValueError(
            f"lanczos needs a symmetric (square) operator, got {op.shape}; "
            "for rectangular A use operator_norm (singular values)")
    if maxiter < 2:
        raise ValueError("lanczos needs maxiter >= 2")
    key = jax.random.PRNGKey(0) if key is None else key
    core = jit_core(op, lambda o: lanczos_pipeline(
        o, tol=tol, maxiter=maxiter, seed_iters=seed_iters))
    y_pair, theta2, hist, k, mvms = core(key)
    res = pack_result(op, "lanczos", y_pair, hist, k, jnp.int32(0), tol,
                      squeeze=False, mvms_single=int(mvms))
    res.eigenvalues = theta2
    return res


# --------------------------------------------------------------------------- #
# LOBPCG
# --------------------------------------------------------------------------- #

def _rayleigh_ritz(s_basis, a_s, nev: int, largest: bool):
    """Ritz pairs of the projected operator on an orthonormal basis.

    Returns the ``nev`` extremal ``(theta, X, AX)`` with theta ascending;
    ``AX`` comes free from the already-computed ``A @ basis``.
    """
    m_proj = s_basis.T @ a_s
    m_proj = 0.5 * (m_proj + m_proj.T)
    theta, c_mat = jnp.linalg.eigh(m_proj)
    sel = slice(-nev, None) if largest else slice(None, nev)
    c_sel = c_mat[:, sel]
    return theta[sel], s_basis @ c_sel, a_s @ c_sel


@solver_core
def _lobpcg_core(op: LinearOperator, x0, key, *, tol: float, maxiter: int,
                 largest: bool):
    nev = x0.shape[1]
    x_blk, _ = jnp.linalg.qr(x0)
    ax_blk = op.matvec(x_blk, jax.random.fold_in(key, 0))
    theta, x_blk, ax_blk = _rayleigh_ritz(x_blk, ax_blk, nev, largest)
    rel0 = col_norms(ax_blk - x_blk * theta[None, :]) \
        / jnp.maximum(jnp.abs(theta), _TINY)

    def cond(state):
        k = state[0]
        rel = state[6]
        return jnp.logical_and(k < maxiter, _unconverged(rel, tol))

    def body(state):
        k, x_blk, ax_blk, p_blk, theta, hist, _rel, mvms = state
        r_blk = ax_blk - x_blk * theta[None, :]
        s_basis, _ = jnp.linalg.qr(
            jnp.concatenate([x_blk, r_blk, p_blk], axis=1))
        # The whole [X | R | P] subspace in ONE batched analog dispatch.
        a_s = op.matvec(s_basis, jax.random.fold_in(key, 1 + k))
        theta, x_new, ax_new = _rayleigh_ritz(s_basis, a_s, nev, largest)
        # Conjugate-direction memory: the part of the step outside old X.
        p_blk = x_new - x_blk @ (x_blk.T @ x_new)
        rel = col_norms(ax_new - x_new * theta[None, :]) \
            / jnp.maximum(jnp.abs(theta), _TINY)
        hist = hist.at[k].set(rel)
        # The 3k-column panel bills as three k-column MVMs (input cost is
        # linear in batch width).
        return k + 1, x_new, ax_new, p_blk, theta, hist, rel, mvms + 3

    state0 = (jnp.int32(0), x_blk, ax_blk, jnp.zeros_like(x_blk), theta,
              init_history(maxiter, nev), rel0, jnp.int32(1))
    out = jax.lax.while_loop(cond, body, state0)
    k, x_blk, theta, hist, mvms = out[0], out[1], out[4], out[5], out[7]
    return x_blk, theta, hist, k, mvms, rel0


def lobpcg_pipeline(
    op: LinearOperator,
    *,
    tol: float = 1e-4,
    maxiter: int = 100,
    largest: bool = True,
):
    """The jit-able LOBPCG core ``(x0, key) -> (X, theta, hist, k, mvms,
    rel0)``; ``x0`` is the (n, k) starting block."""
    return functools.partial(_lobpcg_core, op, tol=tol, maxiter=maxiter,
                             largest=largest)


def lobpcg(
    A,
    k: int = 1,
    *,
    which: str = "largest",
    tol: float = 1e-4,
    maxiter: int = 100,
    x0: Optional[jnp.ndarray] = None,
    key: Optional[jax.Array] = None,
) -> SolveResult:
    """``k`` extremal eigenpairs of a symmetric operator by LOBPCG.

    ``which`` is ``"largest"`` or ``"smallest"``.  Each iteration costs one
    batched 3k-column matvec against the programmed image (billed as three
    k-column MVMs).  Returns ``x`` as the (n, k) eigenvector block (or (n,)
    for ``k=1``) and the estimates in ``result.eigenvalues`` (ascending).
    """
    op = as_operator(A)
    m_, n_ = op.shape
    if m_ != n_:
        raise ValueError(
            f"lobpcg needs a symmetric (square) operator, got {op.shape}")
    if which not in ("largest", "smallest"):
        raise ValueError(f"which must be 'largest' or 'smallest', got "
                         f"{which!r}")
    if not 1 <= k <= n_ // 3:
        raise ValueError(
            f"lobpcg needs 1 <= k <= n//3 (the [X|R|P] subspace must fit), "
            f"got k={k} for n={n_}")
    key = jax.random.PRNGKey(0) if key is None else key
    squeeze = x0 is not None and x0.ndim == 1
    if x0 is None:
        x0b = jax.random.normal(jax.random.fold_in(key, 900_009), (n_, k),
                                jnp.float32)
    else:
        x0b = (x0[:, None] if squeeze else x0).astype(jnp.float32)
        if x0b.shape != (n_, k):
            raise ValueError(f"x0 has shape {x0b.shape}, expected ({n_}, {k})")
    squeeze = squeeze or (x0 is None and k == 1)
    core = jit_core(op, lambda o: lobpcg_pipeline(
        o, tol=tol, maxiter=maxiter, largest=(which == "largest")))
    x_blk, theta, hist, it, mvms, rel0 = core(x0b, key)
    res = pack_result(op, "lobpcg", x_blk, hist, it, mvms, tol,
                      squeeze=squeeze, rel0=rel0)
    res.eigenvalues = theta
    return res


# --------------------------------------------------------------------------- #
# Rectangular feedback: ||A||_2 for PDHG step sizing
# --------------------------------------------------------------------------- #

def _augmented(op: LinearOperator) -> LinearOperator:
    """The symmetric augmentation ``H = [[0, A], [A', 0]]`` of a rectangular
    operator: ``eig(H) = +/- singular values of A``.  One H-matvec is one
    forward plus one transposed analog MVM against the same image."""
    m, n = op.shape

    def aug_mv(v, key):
        top = op.matvec(v[m:], jax.random.fold_in(key, 0))
        bot = op.rmatvec(v[:m], jax.random.fold_in(key, 1))
        return jnp.concatenate([top, bot], axis=0)

    return LinearOperator(
        matvec=aug_mv, rmatvec=aug_mv, shape=(m + n, m + n),
        write_stats=op.write_stats, input_stats=op.input_stats,
        input_stats_t=op.input_stats_t, dense=None, analog=op.analog)


def operator_norm(
    A,
    *,
    tol: float = 1e-3,
    maxiter: int = 32,
    key: Optional[jax.Array] = None,
) -> float:
    """``||A||_2`` (the largest singular value) of a rectangular operator.

    Runs :func:`lanczos` on the symmetric augmentation ``[[0, A], [A', 0]]``
    -- each step is one forward + one transposed MVM, like one PDHG
    iteration -- and converges quadratically faster than the plain power
    method :func:`repro.solvers.pdhg` defaults to.  Typical use::

        step = 0.9 / operator_norm(A_analog, key=key)
        res = pdhg(A_analog, b, c, tau=step, sigma=step)
    """
    op = as_operator(A)
    if op.rmatvec is None:
        raise ValueError("operator_norm needs an operator with rmatvec")
    res = lanczos(_augmented(op), tol=tol, maxiter=maxiter, key=key)
    return float(res.eigenvalues[1])
