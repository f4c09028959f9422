"""Primal-dual hybrid gradient (Chambolle-Pock) for linear programs.

The companion paper ("From GPUs to RRAMs: Distributed In-Memory Primal-Dual
Hybrid Gradient Method for Solving Large-Scale Linear Optimization Problems",
PAPERS.md) shows the SAME program-once crossbar image that serves linear
*systems* also serves linear *optimization*: PDHG touches the constraint
matrix only through ``A @ x`` and ``A.T @ y``, both of which the engine now
runs as corrected analog executions against one programmed image
(:meth:`~repro.engine.AnalogEngine.mvm` / ``rmvm``).  The LP solved here is
the standard-form problem

    min  c'x   s.t.  A x = b,  x >= 0,           A (m, n), m <= n typical

whose saddle form  min_{x>=0} max_y  c'x + y'(Ax - b)  yields the iteration

    x_{k+1} = proj_+( x_k - tau * (c + A'y_k) )          (1 rmatvec)
    y_{k+1} = y_k + sigma * (A (2 x_{k+1} - x_k) - b)    (1 matvec)

convergent for ``tau * sigma * ||A||_2^2 < 1``.  The step sizes default to
``tau = sigma = eta / ||A||_2`` with ``||A||_2`` estimated matvec-only by
power iteration on ``A.T A`` (each power step is one matvec + one rmatvec
against the programmed image, billed to the ledger as batch-1 setup MVMs).

Convergence is tracked per column with the standard PDLP-style KKT residual

    kkt = max( ||Ax - b|| / (1 + ||b||),                  primal feasibility
               ||proj_+(-(c + A'y))|| / (1 + ||c||),      dual feasibility
               |c'x + b'y| / (1 + |c'x| + |b'y|) )        duality gap

(the dual of the LP above is ``max -b'y  s.t.  A'y >= -c``), and the whole
solve -- step-size estimate, ``lax.while_loop`` early stopping, residual
history -- traces into ONE jitted computation.  ``A x_{k+1}`` is carried by
the exact recurrence ``A x_{k+1} = (A x_bar + A x_k) / 2``, so the KKT check
costs no extra MVMs.

Multi-RHS batching solves one LP per column of ``(b, c)`` panels
simultaneously; every inner product and test is per-column, so a batched
solve equals the stacked single-column solves on a digital operator.

Like every solver in :mod:`repro.solvers` this is matvec-only and runs
unchanged across ``local`` / ``streamed`` / ``distributed`` execution and
both backends -- including ``resident=False`` distributed producers, where a
>= 65,536^2 LP is solved with no A-sized array ever allocated (the transposed
scan re-encodes blocks exactly like the forward one; see
DESIGN.md section 5).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .base import (LinearOperator, SolveResult, as_operator, col_norms,
                   init_history, jit_core, pack_result)

__all__ = ["pdhg", "pdhg_pipeline", "random_feasible_lp"]

_TINY = 1e-30


def random_feasible_lp(
    key: jax.Array,
    m: int,
    n: int,
    batch: int = 1,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """A random standard-form LP with a KNOWN optimal primal-dual pair.

    Construction: draw ``A`` (m, n) Gaussian, split a Gaussian vector ``u``
    into the complementary pair ``x* = max(u, 0)`` / ``s = max(-u, 0)``
    (``s'x* = 0`` by construction), draw ``y*`` and set ``b = A x*``,
    ``c = A'y* + s``.  Then ``x*`` is primal feasible, ``(y*, s)`` is dual
    feasible (``c - A'y* = s >= 0``) and complementary slackness holds, so
    ``x*`` / ``y*`` are optimal with objective ``c'x* = b'y*`` -- an exact
    target for solver tests without running an external LP oracle.

    Returns ``(a, b, c, x_star, y_star)``; the vector outputs are squeezed to
    1-D when ``batch == 1``.
    """
    ka, ku, ky = jax.random.split(key, 3)
    a = jax.random.normal(ka, (m, n), jnp.float32) / jnp.sqrt(float(n))
    u = jax.random.normal(ku, (n, batch), jnp.float32)
    x_star = jnp.maximum(u, 0.0)
    s = jnp.maximum(-u, 0.0)
    y_star = jax.random.normal(ky, (m, batch), jnp.float32)
    b = a @ x_star
    c = a.T @ y_star + s
    if batch == 1:
        return a, b[:, 0], c[:, 0], x_star[:, 0], y_star[:, 0]
    return a, b, c, x_star, y_star


def _power_norm(op: LinearOperator, key: jax.Array, iters: int) -> jnp.ndarray:
    """||A||_2 estimate by power iteration on A.T A, matvec-only.

    Each step is one matvec + one rmatvec against the programmed image (2
    batch-1 MVMs); the dominant eigenvalue of A.T A is ||A||_2^2.
    """
    v0 = jax.random.normal(jax.random.fold_in(key, 0), (op.shape[1], 1),
                           jnp.float32)
    v0 = v0 / jnp.maximum(col_norms(v0), _TINY)

    def body(i, carry):
        v, _ = carry
        w = op.matvec(v, jax.random.fold_in(key, 1 + 2 * i))
        u = op.rmatvec(w, jax.random.fold_in(key, 2 + 2 * i))
        lam = col_norms(u)[0]
        return u / jnp.maximum(lam, _TINY), lam

    _, lam = jax.lax.fori_loop(0, iters, body, (v0, jnp.float32(0.0)))
    return jnp.sqrt(jnp.maximum(lam, _TINY))


def _pdhg_core(op: LinearOperator, b, c, x0, y0, key, *, tau, sigma, eta,
               tol: float, maxiter: int, power_iters: int,
               divergence: Optional[float] = None):
    batch = b.shape[1]
    # Static switch, as in krylov._cg_core: divergence=None keeps the carry
    # and jaxpr identical to the plain core; a factor adds best-KKT tracking
    # and NaN/spike early exit for fault-tolerant wrappers.
    track = divergence is not None
    bn = 1.0 + col_norms(b)
    cn = 1.0 + col_norms(c)

    if tau is None or sigma is None:
        norm_a = _power_norm(op, jax.random.fold_in(key, 900_003),
                             power_iters)
        step = eta / norm_a
        tau_v = step if tau is None else jnp.float32(tau)
        sigma_v = step if sigma is None else jnp.float32(sigma)
        # Each power step is one forward + one transposed batch-1 MVM; they
        # are billed separately (the two directions' input writes differ).
        pi_mvms = jnp.int32(power_iters)
    else:
        tau_v, sigma_v = jnp.float32(tau), jnp.float32(sigma)
        pi_mvms = jnp.int32(0)

    def kkt(x, y, ax, aty):
        primal = col_norms(ax - b) / bn
        dual = col_norms(jnp.maximum(-(c + aty), 0.0)) / cn
        pobj = jnp.sum(c * x, axis=0)
        dobj = -jnp.sum(b * y, axis=0)
        gap = jnp.abs(pobj - dobj) / (1.0 + jnp.abs(pobj) + jnp.abs(dobj))
        return jnp.maximum(jnp.maximum(primal, dual), gap)

    aty0 = op.rmatvec(y0, jax.random.fold_in(key, 0))
    ax0 = op.matvec(x0, jax.random.fold_in(key, 1))
    rel0 = kkt(x0, y0, ax0, aty0)

    def cond(state):
        if track:
            k, _x, _y, _ax, _aty, _h, rel, best, _m = state
            spike = jnp.logical_or(
                jnp.any(jnp.isnan(rel)),
                jnp.any(rel > divergence * jnp.maximum(best, tol)))
            healthy = jnp.logical_not(spike)
        else:
            k, _x, _y, _ax, _aty, _h, rel, _m = state
            healthy = True
        # NaN-robust: a NaN residual counts as not converged.
        return jnp.logical_and(
            jnp.logical_and(k < maxiter,
                            jnp.logical_not(jnp.all(rel <= tol))), healthy)

    def body(state):
        if track:
            k, x, y, ax, aty, hist, _rel, best, mvms = state
        else:
            k, x, y, ax, aty, hist, _rel, mvms = state
        x_new = jnp.maximum(x - tau_v * (c + aty), 0.0)
        x_bar = 2.0 * x_new - x
        ax_bar = op.matvec(x_bar, jax.random.fold_in(key, 2 + 2 * k))
        y_new = y + sigma_v * (ax_bar - b)
        aty_new = op.rmatvec(y_new, jax.random.fold_in(key, 3 + 2 * k))
        # A x_{k+1} from the over-relaxation identity x_bar = 2 x_{k+1} - x_k
        # -- exact for a linear digital operator, an averaged (noise-damped)
        # estimate for the analog one; no extra MVM either way.
        ax_new = 0.5 * (ax_bar + ax)
        rel = kkt(x_new, y_new, ax_new, aty_new)
        hist = hist.at[k].set(rel)
        if track:
            best = jnp.minimum(best, rel)
            return (k + 1, x_new, y_new, ax_new, aty_new, hist, rel, best,
                    mvms + 1)
        return k + 1, x_new, y_new, ax_new, aty_new, hist, rel, mvms + 1

    hist0 = init_history(maxiter, batch)
    if track:
        state0 = (jnp.int32(0), x0, y0, ax0, aty0, hist0, rel0, rel0,
                  jnp.int32(1))
        k, x, y, _ax, _aty, hist, _rel, _best, mvms = jax.lax.while_loop(
            cond, body, state0)
    else:
        state0 = (jnp.int32(0), x0, y0, ax0, aty0, hist0, rel0, jnp.int32(1))
        k, x, y, _ax, _aty, hist, _rel, mvms = jax.lax.while_loop(
            cond, body, state0)
    # mvms counts FORWARD full-batch MVMs (init + 1/iter); the transposed
    # count mirrors it exactly (init rmatvec + 1/iter).
    return x, y, hist, k, mvms, pi_mvms, rel0


def pdhg_pipeline(
    op: LinearOperator,
    *,
    tau: Optional[float] = None,
    sigma: Optional[float] = None,
    eta: float = 0.9,
    tol: float = 1e-4,
    maxiter: int = 2000,
    power_iters: int = 16,
    divergence: Optional[float] = None,
):
    """The jit-able PDHG core ``(b, c, x0, y0, key) -> (...)``.

    The whole-solve pipeline :func:`pdhg` jits (step-size power iteration,
    while-loop, KKT residuals), exposed so jaxpr-level tooling
    (:mod:`repro.analysis.pipelines`, the invariant gate) can trace the
    exact computation a solve dispatches.  All vector operands are
    (m, batch) / (n, batch) panels.  ``divergence`` (a factor) adds in-loop
    fault detection -- exit on NaN or a KKT residual above ``divergence`` x
    the best seen (see DESIGN.md sections 10 and 12).
    """
    return functools.partial(
        _pdhg_core, op, tau=tau, sigma=sigma, eta=eta, tol=tol,
        maxiter=maxiter, power_iters=power_iters, divergence=divergence)


def pdhg(
    A,
    b: jnp.ndarray,
    c: jnp.ndarray,
    *,
    tol: float = 1e-4,
    maxiter: int = 2000,
    eta: float = 0.9,
    tau: Optional[float] = None,
    sigma: Optional[float] = None,
    x0: Optional[jnp.ndarray] = None,
    y0: Optional[jnp.ndarray] = None,
    key: Optional[jax.Array] = None,
    power_iters: int = 16,
    divergence: Optional[float] = None,
) -> SolveResult:
    """Solve ``min c'x  s.t.  A x = b, x >= 0`` by PDHG, matvec/rmatvec-only.

    ``A`` is anything :func:`~repro.solvers.as_operator` accepts that has an
    ``rmatvec`` (an :class:`~repro.engine.AnalogMatrix`, a dense array, or a
    bare matvec with ``rmatvec=`` supplied) -- one iteration is exactly one
    corrected ``A.T @ y`` plus one corrected ``A @ x`` against the programmed
    image.  ``b`` is (m,) / (m, batch) and ``c`` (n,) / (n, batch); each
    column is an independent LP.  ``tau``/``sigma`` default to
    ``eta / ||A||_2`` with the norm estimated by ``power_iters`` steps of
    power iteration on ``A.T A`` (billed as ``power_iters`` forward plus
    ``power_iters`` transposed batch-1 setup MVMs, each at its own
    input-write rate).  Returns a :class:`SolveResult` whose ``x`` is the primal
    solution, ``dual`` the dual variable ``y``, and ``residuals`` the
    per-iteration KKT residual (max of primal/dual infeasibility and the
    relative duality gap); the ledger splits forward and transposed MVMs.
    """
    op = as_operator(A)
    if op.rmatvec is None:
        raise ValueError(
            "pdhg needs an operator with rmatvec (A.T @ y): pass an "
            "AnalogMatrix / dense array, or as_operator(mv, shape=..., "
            "rmatvec=...)")
    m, n = op.shape
    squeeze = b.ndim == 1
    if (c.ndim == 1) != squeeze:
        raise ValueError("b and c must both be vectors or both be panels")
    bb = (b[:, None] if squeeze else b).astype(jnp.float32)
    cc = (c[:, None] if squeeze else c).astype(jnp.float32)
    if bb.shape[0] != m or cc.shape[0] != n:
        raise ValueError(
            f"b has {bb.shape[0]} rows and c {cc.shape[0]} for an operator "
            f"of shape {op.shape}; expected ({m}, batch) and ({n}, batch)")
    if bb.shape[1] != cc.shape[1]:
        raise ValueError(
            f"b batch {bb.shape[1]} != c batch {cc.shape[1]}")
    x0b = jnp.zeros_like(cc) if x0 is None else \
        (x0[:, None] if squeeze else x0).astype(jnp.float32)
    y0b = jnp.zeros_like(bb) if y0 is None else \
        (y0[:, None] if squeeze else y0).astype(jnp.float32)
    key = jax.random.PRNGKey(0) if key is None else key

    core = jit_core(op, lambda o: pdhg_pipeline(
        o, tau=tau, sigma=sigma, eta=eta, tol=tol, maxiter=maxiter,
        power_iters=power_iters, divergence=divergence))
    x, y, hist, k, mvms, pi_mvms, rel0 = core(bb, cc, x0b, y0b, key)
    res = pack_result(op, "pdhg", x, hist, k, mvms, tol, squeeze,
                      mvms_single=int(pi_mvms), rel0=rel0, mvms_t=int(mvms),
                      mvms_single_t=int(pi_mvms))
    res.dual = y[:, 0] if squeeze else y
    return res
