"""Linearized ADMM for box-constrained quadratic programs, matvec+rmatvec.

Beside :mod:`~repro.solvers.pdhg`'s equality-constrained LPs, the other
workhorse of the first-order-on-analog literature is the box-constrained QP

    min_x  (1/2) || A x - b ||^2  +  q' x      s.t.  lo <= x <= hi

(portfolio construction, MPC, bounded deblurring, ...).  The splitting is
``f(x) = (1/2)||Ax - b||^2 + q'x`` against the box indicator ``g(z)`` with
the consensus constraint ``x = z``; the x-update LINEARIZES ``f`` around the
current iterate, so each iteration is exactly

    grad  = A'(A x - b) + q                      # one matvec + one rmatvec
    x_new = x - mu * (grad + rho * (x - z + u))  # linearized prox step
    z_new = clip(x_new + u, lo, hi)              # exact box projection
    u_new = u + x_new - z_new                    # scaled dual ascent

-- one forward plus one transposed corrected MVM against the ONE programmed
image, the same per-iteration budget as PDHG and the bidiagonalization
solvers.  ``mu < 1 / (||A||_2^2 + rho)`` guarantees the linearized step is a
majorizer; the default estimates ``||A||_2`` with the same power iteration
PDHG uses (or feed :func:`repro.solvers.operator_norm`'s sharper Lanczos
estimate through ``mu=`` yourself).

Residual semantics: the recorded history is the digitally-recomputable KKT
measure at the primal iterate,

    ( || x - clip(x - grad(x), lo, hi) ||  +  || x - z || ) / (1 + ||x||)

i.e. projected-gradient stationarity plus consensus infeasibility.  The
gradient in the recorded value is the one the iteration just computed (so
it sees analog noise); the contract suite recomputes the same formula
digitally from the returned ``(x, dual=z)``.  The feasible split copy ``z``
is returned in ``SolveResult.dual`` -- take ``res.dual`` when a hard
in-box iterate is required, ``res.x`` for the stationarity-optimal one.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .base import (LinearOperator, SolveResult, as_operator, col_norms,
                   init_history, jit_core, pack_result, solver_core)
from .pdhg import _power_norm

__all__ = ["admm", "admm_pipeline", "random_box_qp"]

_TINY = 1e-30


def random_box_qp(
    key: jax.Array,
    m: int,
    n: int,
    batch: int = 1,
    active_frac: float = 0.3,
) -> Tuple[jnp.ndarray, ...]:
    """A random box-constrained QP with a KNOWN optimal point.

    Construction: draw ``A`` (m, n) Gaussian and an optimal ``x*`` in the
    box ``[-1, 1]^n`` with ~``active_frac`` of its components ON the bounds.
    KKT for the box-QP says the gradient at the optimum satisfies
    ``grad_i >= 0`` where ``x*_i = lo_i``, ``<= 0`` where ``x*_i = hi_i``
    and ``= 0`` in the interior -- so draw such a ``g``, pick any ``b``, and
    back out ``q = g - A'(A x* - b)``.  Then ``x*`` is exactly optimal: an
    oracle target without an external QP solver.

    Returns ``(a, b, q, lo, hi, x_star)``; vector outputs are squeezed to
    1-D when ``batch == 1``.
    """
    ka, kx, kg, kb, kw = jax.random.split(key, 5)
    a = jax.random.normal(ka, (m, n), jnp.float32) / jnp.sqrt(float(n))
    lo = -jnp.ones((n,), jnp.float32)
    hi = jnp.ones((n,), jnp.float32)
    interior = jax.random.uniform(kx, (n, batch), jnp.float32,
                                  minval=-0.9, maxval=0.9)
    side = jax.random.uniform(kw, (n, batch)) < 0.5
    bound = jnp.where(side, lo[:, None], hi[:, None])
    active = jax.random.uniform(kg, (n, batch)) < active_frac
    x_star = jnp.where(active, bound, interior)
    # Multiplier magnitudes; sign follows which bound is active.
    mult = jnp.abs(jax.random.normal(kg, (n, batch), jnp.float32))
    grad = jnp.where(active, jnp.where(side, mult, -mult), 0.0)
    b = jax.random.normal(kb, (m, batch), jnp.float32)
    q = grad - a.T @ (a @ x_star - b)
    if batch == 1:
        return a, b[:, 0], q[:, 0], lo, hi, x_star[:, 0]
    return a, b, q, lo, hi, x_star


@solver_core
def _admm_core(op: LinearOperator, b, q, x0, key, *, lo, hi, rho: float,
               mu, tol: float, maxiter: int, power_iters: int):
    batch = b.shape[1]
    lo_c = lo[:, None]
    hi_c = hi[:, None]

    if mu is None:
        norm_a = _power_norm(op, jax.random.fold_in(key, 900_005),
                             power_iters)
        mu_v = 1.0 / (1.05 * (jnp.square(norm_a) + rho))
        # Each power step is one forward + one transposed batch-1 MVM,
        # billed separately from the solve's full-batch iterations.
        pi_mvms = jnp.int32(power_iters)
    else:
        mu_v = jnp.float32(mu)
        pi_mvms = jnp.int32(0)

    def kkt(x, z, grad):
        stat = col_norms(x - jnp.clip(x - grad, lo_c, hi_c))
        feas = col_norms(x - z)
        return (stat + feas) / (1.0 + col_norms(x))

    z0 = jnp.clip(x0, lo_c, hi_c)
    u0 = jnp.zeros_like(x0)
    ax0 = op.matvec(x0, jax.random.fold_in(key, 0))
    grad0 = op.rmatvec(ax0 - b, jax.random.fold_in(key, 1)) + q
    rel0 = kkt(x0, z0, grad0)

    def cond(state):
        k = state[0]
        rel = state[6]
        return jnp.logical_and(k < maxiter,
                               jnp.logical_not(jnp.all(rel <= tol)))

    def body(state):
        k, x, z, u, grad, hist, _rel, mvms = state
        x = x - mu_v * (grad + rho * (x - z + u))
        z = jnp.clip(x + u, lo_c, hi_c)
        u = u + x - z
        # Gradient at the NEW iterate -- the iteration's one matvec+rmatvec
        # pair -- so the recorded KKT residual is evaluated at exactly the
        # (x, z) this state returns (digitally recomputable by the contract
        # suite from the final result).
        ax = op.matvec(x, jax.random.fold_in(key, 2 + 2 * k))
        grad = op.rmatvec(ax - b, jax.random.fold_in(key, 3 + 2 * k)) + q
        rel = kkt(x, z, grad)
        hist = hist.at[k].set(rel)
        return k + 1, x, z, u, grad, hist, rel, mvms + 1

    hist0 = init_history(maxiter, batch)
    state0 = (jnp.int32(0), x0, z0, u0, grad0, hist0, rel0, jnp.int32(1))
    out = jax.lax.while_loop(cond, body, state0)
    k, x, z, hist, mvms = out[0], out[1], out[2], out[5], out[7]
    return x, z, hist, k, mvms, pi_mvms, rel0


def admm_pipeline(
    op: LinearOperator,
    *,
    lo: jnp.ndarray,
    hi: jnp.ndarray,
    rho: float = 1.0,
    mu: Optional[float] = None,
    tol: float = 1e-4,
    maxiter: int = 500,
    power_iters: int = 16,
):
    """The jit-able ADMM core ``(b, q, x0, key) -> (x, z, hist, k, mvms,
    pi_mvms, rel0)``.

    Exposed for the invariant gate; ``b`` is (m, batch), ``q``/``x0``
    (n, batch), ``lo``/``hi`` (n,) bound vectors.  ``mu=None`` adds the
    power-iteration ``||A||_2`` estimate to the traced program.
    """
    return functools.partial(_admm_core, op, lo=lo, hi=hi, rho=rho, mu=mu,
                             tol=tol, maxiter=maxiter,
                             power_iters=power_iters)


def admm(
    A,
    b: jnp.ndarray,
    q: jnp.ndarray,
    *,
    lo,
    hi,
    rho: float = 1.0,
    mu: Optional[float] = None,
    tol: float = 1e-4,
    maxiter: int = 500,
    x0: Optional[jnp.ndarray] = None,
    key: Optional[jax.Array] = None,
    power_iters: int = 16,
) -> SolveResult:
    """Solve ``min (1/2)||Ax - b||^2 + q'x  s.t.  lo <= x <= hi`` by
    linearized ADMM: one corrected matvec + one corrected rmatvec per
    iteration against the programmed image.

    ``b`` is (m,) / (m, batch), ``q`` (n,) / (n, batch) -- each column an
    independent QP over the shared bounds ``lo``/``hi`` (scalars or (n,)
    vectors).  ``rho`` is the consensus penalty; ``mu`` the linearized step
    (default ``1 / (1.05 (||A||_2^2 + rho))`` with the norm from
    ``power_iters`` power-iteration steps, billed to the ledger).  Returns a
    :class:`SolveResult` with the stationarity iterate in ``x``, the
    box-feasible split copy in ``dual``, and the KKT residual history
    (projected-gradient stationarity + consensus gap, relative).
    """
    op = as_operator(A)
    if op.rmatvec is None:
        raise ValueError(
            "admm needs an operator with rmatvec (A.T @ u): pass an "
            "AnalogMatrix / dense array, or as_operator(mv, shape=..., "
            "rmatvec=...)")
    m, n = op.shape
    squeeze = b.ndim == 1
    if (q.ndim == 1) != squeeze:
        raise ValueError("b and q must both be vectors or both be panels")
    bb = (b[:, None] if squeeze else b).astype(jnp.float32)
    qq = (q[:, None] if squeeze else q).astype(jnp.float32)
    if bb.shape[0] != m or qq.shape[0] != n:
        raise ValueError(
            f"b has {bb.shape[0]} rows and q {qq.shape[0]} for an operator "
            f"of shape {op.shape}; expected ({m}, batch) and ({n}, batch)")
    if bb.shape[1] != qq.shape[1]:
        raise ValueError(f"b batch {bb.shape[1]} != q batch {qq.shape[1]}")
    lo_v = jnp.broadcast_to(jnp.asarray(lo, jnp.float32), (n,))
    hi_v = jnp.broadcast_to(jnp.asarray(hi, jnp.float32), (n,))
    if bool(jnp.any(lo_v > hi_v)):
        raise ValueError("box is empty: lo > hi somewhere")
    x0b = jnp.zeros_like(qq) if x0 is None else \
        (x0[:, None] if squeeze else x0).astype(jnp.float32)
    key = jax.random.PRNGKey(0) if key is None else key

    core = jit_core(op, lambda o: admm_pipeline(
        o, lo=lo_v, hi=hi_v, rho=rho, mu=mu, tol=tol, maxiter=maxiter,
        power_iters=power_iters))
    x, z, hist, k, mvms, pi_mvms, rel0 = core(bb, qq, x0b, key)
    res = pack_result(op, "admm", x, hist, k, mvms, tol, squeeze,
                      mvms_single=int(pi_mvms), rel0=rel0, mvms_t=int(mvms),
                      mvms_single_t=int(pi_mvms))
    res.dual = z[:, 0] if squeeze else z
    return res
