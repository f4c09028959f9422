"""Krylov-subspace solvers: CG (SPD), BiCGSTAB and GMRES(m) (general).

All three touch ``A`` only through ``matvec(v, key)``, so they run unchanged
against every :class:`~repro.engine.AnalogEngine` execution mode (``local`` /
``streamed`` / ``distributed``) and backend.  Multi-RHS panels ``b`` of shape
(n, batch) are solved simultaneously -- every inner product, step length and
convergence test is per-column -- and the whole solve (including the
``lax.while_loop`` early stopping) traces into one jitted computation.

Distributed operands stay distributed: a producer-driven
``execution="distributed"`` handle's matvec emits its output row-sharded from
shard_map, and since every reduction here is a per-column ``jnp.sum`` /
norm (scalars replicate, panels never reshape), GSPMD propagates the row
sharding through the whole while_loop -- a sharded CG solve is ONE compiled
program whose x/r/p panels never gather onto a single device.

Analog caveat, and why these still work here: each MVM carries fresh DAC
noise, so Krylov recurrences see a slightly *inexact* operator.  With the
two-tier error correction on, the per-MVM relative error is ~1e-3, which
inexact-Krylov theory tolerates until the residual approaches the noise
floor; solves to tolerances below that floor should wrap the method in
:func:`repro.solvers.refinement.refine` (digital outer residual).

``backend="pallas"`` fuses CG's twin axpy (x/r update) into
:func:`repro.kernels.solver_cg_update`.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .base import (LinearOperator, SolveResult, as_operator, col_norms,
                   init_history, jit_core, pack_result, use_pallas)

__all__ = ["cg", "bicgstab", "gmres", "cg_pipeline"]

_TINY = 1e-30


def _cdot(u: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """Per-column inner products of (n, batch) panels -> (batch,)."""
    return jnp.sum(u * v, axis=0)


def _safe(d: jnp.ndarray) -> jnp.ndarray:
    """Sign-preserving division guard (BiCGSTAB scalars are signed)."""
    return jnp.where(jnp.abs(d) < _TINY, _TINY, d)


def _unconverged(rel: jnp.ndarray, tol: float) -> jnp.ndarray:
    """NaN-robust: a NaN residual (breakdown) counts as not converged."""
    return jnp.logical_not(jnp.all(rel <= tol))


def _prep(b, x0):
    squeeze = b.ndim == 1
    bb = (b[:, None] if squeeze else b).astype(jnp.float32)
    x0b = jnp.zeros_like(bb) if x0 is None else \
        (x0[:, None] if squeeze else x0).astype(jnp.float32)
    return bb, x0b, squeeze


# --------------------------------------------------------------------------- #
# Conjugate gradients (SPD)
# --------------------------------------------------------------------------- #

def _cg_core(op: LinearOperator, b, x0, key, *, tol: float, maxiter: int,
             use_pallas: bool, divergence: Optional[float] = None):
    batch = b.shape[1]
    bn = jnp.maximum(col_norms(b), _TINY)
    r0 = b - op.matvec(x0, jax.random.fold_in(key, 0))
    rho0 = _cdot(r0, r0)
    rel0 = jnp.sqrt(rho0) / bn
    # Divergence tracking is a python-static switch: with divergence=None the
    # carry and jaxpr are byte-identical to the plain core (the invariant
    # gate pins that trace); with a factor set, the loop also carries the
    # best residual seen and exits on NaN or rel > divergence * best --
    # instead of burning maxiter NaN iterations after a device fault.
    track = divergence is not None

    def cond(state):
        if track:
            k, _x, _r, _p, _rho, _h, rel, best, _m = state
            spike = jnp.logical_or(
                jnp.any(jnp.isnan(rel)),
                jnp.any(rel > divergence * jnp.maximum(best, tol)))
            healthy = jnp.logical_not(spike)
        else:
            k, _x, _r, _p, _rho, _h, rel, _m = state
            healthy = True
        return jnp.logical_and(
            jnp.logical_and(k < maxiter, _unconverged(rel, tol)), healthy)

    def body(state):
        if track:
            k, x, r, p, rho, hist, _rel, best, mvms = state
        else:
            k, x, r, p, rho, hist, _rel, mvms = state
        ap = op.matvec(p, jax.random.fold_in(key, 1 + k))
        alpha = rho / jnp.maximum(_cdot(p, ap), _TINY)
        if use_pallas:
            from repro.kernels import solver_cg_update
            x, r = solver_cg_update(x, r, p, ap, alpha)
        else:
            x = x + alpha[None, :] * p
            r = r - alpha[None, :] * ap
        rho_new = _cdot(r, r)
        beta = rho_new / jnp.maximum(rho, _TINY)
        p = r + beta[None, :] * p
        rel = jnp.sqrt(rho_new) / bn
        hist = hist.at[k].set(rel)
        if track:
            best = jnp.minimum(best, rel)
            return k + 1, x, r, p, rho_new, hist, rel, best, mvms + 1
        return k + 1, x, r, p, rho_new, hist, rel, mvms + 1

    hist0 = init_history(maxiter, batch)
    if track:
        state0 = (jnp.int32(0), x0, r0, r0, rho0, hist0, rel0, rel0,
                  jnp.int32(1))
        k, x, _r, _p, _rho, hist, _rel, _best, mvms = jax.lax.while_loop(
            cond, body, state0)
    else:
        state0 = (jnp.int32(0), x0, r0, r0, rho0, hist0, rel0, jnp.int32(1))
        k, x, _r, _p, _rho, hist, _rel, mvms = jax.lax.while_loop(
            cond, body, state0)
    return x, hist, k, mvms, rel0


def cg_pipeline(
    op: LinearOperator,
    *,
    tol: float = 1e-6,
    maxiter: int = 200,
    backend: Optional[str] = None,
    divergence: Optional[float] = None,
):
    """The jit-able CG core ``(b, x0, key) -> (x, hist, k, mvms, rel0)``.

    This is the whole-solve pipeline :func:`cg` jits -- exposed so
    jaxpr-level tooling (:mod:`repro.analysis.pipelines`, the invariant
    gate) can trace the exact computation a solve dispatches.  ``b`` and
    ``x0`` are (n, batch) panels.  ``divergence`` (a factor, e.g. 10) adds
    in-loop fault detection: exit as soon as any column's residual is NaN or
    exceeds ``divergence`` x the best residual seen -- the hook
    :mod:`repro.reliability.ft_solve` uses to stop a faulted segment early.
    See DESIGN.md sections 10 and 12.
    """
    return functools.partial(_cg_core, op, tol=tol, maxiter=maxiter,
                             use_pallas=use_pallas(backend),
                             divergence=divergence)


def cg(
    A,
    b: jnp.ndarray,
    *,
    tol: float = 1e-6,
    maxiter: int = 200,
    x0: Optional[jnp.ndarray] = None,
    key: Optional[jax.Array] = None,
    backend: Optional[str] = None,
    divergence: Optional[float] = None,
) -> SolveResult:
    """Conjugate gradients for SPD ``A``; one MVM per iteration.

    ``divergence`` enables early exit on NaN/residual-spike (see
    :func:`cg_pipeline`); the default None keeps the classic trace.
    """
    op = as_operator(A)
    bb, x0b, squeeze = _prep(b, x0)
    key = jax.random.PRNGKey(0) if key is None else key
    core = jit_core(op, lambda o: cg_pipeline(
        o, tol=tol, maxiter=maxiter, backend=backend, divergence=divergence))
    x, hist, k, mvms, rel0 = core(bb, x0b, key)
    return pack_result(op, "cg", x, hist, k, mvms, tol, squeeze, rel0=rel0)


# --------------------------------------------------------------------------- #
# BiCGSTAB (general square A)
# --------------------------------------------------------------------------- #

def _bicgstab_core(op: LinearOperator, b, x0, key, *, tol: float,
                   maxiter: int):
    batch = b.shape[1]
    bn = jnp.maximum(col_norms(b), _TINY)
    r0 = b - op.matvec(x0, jax.random.fold_in(key, 0))
    rhat = r0                       # fixed shadow residual
    ones = jnp.ones((batch,), jnp.float32)
    zeros_p = jnp.zeros_like(b)

    def cond(state):
        k, _x, _r, _p, _v, _rho, _a, _w, _h, rel, _m = state
        return jnp.logical_and(k < maxiter, _unconverged(rel, tol))

    def body(state):
        k, x, r, p, v, rho, alpha, w, hist, _rel, mvms = state
        rho_new = _cdot(rhat, r)
        beta = (rho_new / _safe(rho)) * (alpha / _safe(w))
        p = r + beta[None, :] * (p - w[None, :] * v)
        v = op.matvec(p, jax.random.fold_in(key, 1 + 2 * k))
        alpha = rho_new / _safe(_cdot(rhat, v))
        s = r - alpha[None, :] * v
        t = op.matvec(s, jax.random.fold_in(key, 2 + 2 * k))
        w = _cdot(t, s) / _safe(_cdot(t, t))
        x = x + alpha[None, :] * p + w[None, :] * s
        r = s - w[None, :] * t
        rel = col_norms(r) / bn
        hist = hist.at[k].set(rel)
        return (k + 1, x, r, p, v, rho_new, alpha, w, hist, rel, mvms + 2)

    rel0 = col_norms(r0) / bn
    state0 = (jnp.int32(0), x0, r0, zeros_p, zeros_p, ones, ones, ones,
              init_history(maxiter, batch), rel0, jnp.int32(1))
    out = jax.lax.while_loop(cond, body, state0)
    k, x, hist, mvms = out[0], out[1], out[8], out[10]
    return x, hist, k, mvms, rel0


def bicgstab(
    A,
    b: jnp.ndarray,
    *,
    tol: float = 1e-6,
    maxiter: int = 200,
    x0: Optional[jnp.ndarray] = None,
    key: Optional[jax.Array] = None,
) -> SolveResult:
    """BiCGSTAB for general square ``A``; two MVMs per iteration."""
    op = as_operator(A)
    bb, x0b, squeeze = _prep(b, x0)
    key = jax.random.PRNGKey(0) if key is None else key
    core = jit_core(op, lambda o: functools.partial(
        _bicgstab_core, o, tol=tol, maxiter=maxiter))
    x, hist, k, mvms, rel0 = core(bb, x0b, key)
    return pack_result(op, "bicgstab", x, hist, k, mvms, tol, squeeze,
                       rel0=rel0)


# --------------------------------------------------------------------------- #
# Restarted GMRES(m) (general square A)
# --------------------------------------------------------------------------- #

def _gmres_cycle(op: LinearOperator, x, r, key, m: int):
    """One Arnoldi(m) + least-squares correction.  Fixed-shape: the Krylov
    basis V is (m+1, n, batch) with unfilled rows zero; projections mask by
    position so the loop carries static shapes."""
    n, batch = r.shape
    beta = col_norms(r)
    V = jnp.zeros((m + 1, n, batch), jnp.float32)
    V = V.at[0].set(r / jnp.maximum(beta, _TINY)[None, :])
    H = jnp.zeros((m + 1, m, batch), jnp.float32)
    rows = jnp.arange(m + 1)

    def arnoldi(j, carry):
        V, H = carry
        vj = jax.lax.dynamic_index_in_dim(V, j, axis=0, keepdims=False)
        w = op.matvec(vj, jax.random.fold_in(key, 10 + j))
        # Classical Gram-Schmidt against the filled basis (rows <= j), twice
        # (CGS2) for fp32 stability at the usual m ~ 20.
        mask = (rows <= j).astype(jnp.float32)[:, None]
        h1 = jnp.einsum("inb,nb->ib", V, w) * mask
        w = w - jnp.einsum("ib,inb->nb", h1, V)
        h2 = jnp.einsum("inb,nb->ib", V, w) * mask
        w = w - jnp.einsum("ib,inb->nb", h2, V)
        hcol = h1 + h2
        hnorm = col_norms(w)
        hcol = hcol + (rows == j + 1).astype(jnp.float32)[:, None] * hnorm
        V = V.at[j + 1].set(w / jnp.maximum(hnorm, _TINY)[None, :])
        H = H.at[:, j].set(hcol)
        return V, H

    V, H = jax.lax.fori_loop(0, m, arnoldi, (V, H))

    # Per-column least squares min ||beta e1 - H y|| via ridge-stabilized
    # normal equations (m x m, tiny next to the MVMs).
    Hb = jnp.moveaxis(H, -1, 0)                     # (batch, m+1, m)
    rhs = jnp.zeros((batch, m + 1), jnp.float32).at[:, 0].set(beta)
    gram = jnp.einsum("bij,bik->bjk", Hb, Hb) \
        + 1e-12 * jnp.eye(m, dtype=jnp.float32)
    hty = jnp.einsum("bij,bi->bj", Hb, rhs)
    y = jnp.linalg.solve(gram, hty[..., None])[..., 0]   # (batch, m)
    dx = jnp.einsum("bj,jnb->nb", y, V[:m])
    return x + dx


def _gmres_core(op: LinearOperator, b, x0, key, *, tol: float, maxiter: int,
                restart: int):
    batch = b.shape[1]
    bn = jnp.maximum(col_norms(b), _TINY)
    ncycles = max(1, -(-maxiter // restart))
    r0 = b - op.matvec(x0, jax.random.fold_in(key, 0))

    def cond(state):
        c, _x, _r, rel, _h, _m = state
        return jnp.logical_and(c < ncycles, _unconverged(rel, tol))

    def body(state):
        c, x, r, _rel, hist, mvms = state
        ckey = jax.random.fold_in(key, 1000 + c)
        x = _gmres_cycle(op, x, r, ckey, restart)
        r = b - op.matvec(x, jax.random.fold_in(ckey, 1))
        rel = col_norms(r) / bn
        hist = hist.at[c].set(rel)
        return c + 1, x, r, rel, hist, mvms + restart + 1

    rel0 = col_norms(r0) / bn
    state0 = (jnp.int32(0), x0, r0, rel0,
              init_history(ncycles, batch), jnp.int32(1))
    c, x, _r, _rel, hist, mvms = jax.lax.while_loop(cond, body, state0)
    return x, hist, c, mvms, rel0


def gmres(
    A,
    b: jnp.ndarray,
    *,
    restart: int = 20,
    tol: float = 1e-6,
    maxiter: int = 200,
    x0: Optional[jnp.ndarray] = None,
    key: Optional[jax.Array] = None,
) -> SolveResult:
    """Restarted GMRES(m) for general square ``A``.

    ``maxiter`` bounds total MVMs (``ceil(maxiter / restart)`` cycles of
    ``restart + 1`` MVMs each); ``SolveResult.iterations`` and the residual
    history are per *cycle*.
    """
    op = as_operator(A)
    bb, x0b, squeeze = _prep(b, x0)
    key = jax.random.PRNGKey(0) if key is None else key
    core = jit_core(op, lambda o: functools.partial(
        _gmres_core, o, tol=tol, maxiter=maxiter, restart=restart))
    x, hist, c, mvms, rel0 = core(bb, x0b, key)
    return pack_result(op, "gmres", x, hist, c, mvms, tol, squeeze, rel0=rel0)
