"""Mixed-precision iterative refinement: analog inner solve, digital outer.

The paper's two-tier error-correction philosophy (cheap analog compute, a thin
exact correction layered on top) lifted to the solver level:

    r_k = b - A x_k          (digital fp32, the EXACT matrix A_tilde + dA)
    d_k ~= A^{-1} r_k        (analog inner solve against the programmed image)
    x_{k+1} = x_k + d_k

The inner solve only needs a crude correction (its error contracts the outer
residual by the factor it achieves), so it runs few iterations at a loose
tolerance entirely on the analog array; the outer loop's exact residual lets
the combination converge *below the analog noise floor* that caps a bare
Krylov/stationary solve.  Costs one digital (n, n) matvec per outer step.

Matvec-only on the analog side; the digital matrix is reconstructed once from
the programmed operands (or passed via ``a_digital`` when the caller has it).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .base import (SolveResult, as_operator, col_norms, dispatched,
                   init_history, pack_result, solver_core, use_pallas)
from .krylov import _cg_core
from .stationary import _stationary_core, spectral_bounds

__all__ = ["refine"]

_TINY = 1e-30


def refine(
    A,
    b: jnp.ndarray,
    *,
    inner: str = "cg",
    inner_iters: int = 8,
    inner_tol: float = 1e-2,
    tol: float = 1e-8,
    maxiter: int = 20,
    omega: Optional[float] = None,
    x0: Optional[jnp.ndarray] = None,
    key: Optional[jax.Array] = None,
    a_digital: Optional[jnp.ndarray] = None,
    backend: Optional[str] = None,
) -> SolveResult:
    """Iterative refinement with an analog inner solver.

    ``inner`` is ``"cg"`` or ``"richardson"`` (each capped at ``inner_iters``
    analog MVM iterations / ``inner_tol``); the outer residual is exact fp32.
    The residual history records the *digital* relative residual after each
    outer correction, so it keeps falling where a pure analog solve plateaus.
    """
    op = as_operator(A)
    if a_digital is None:
        if op.dense is None:
            raise ValueError(
                "refine needs a_digital= for a bare matvec operator")
        a_digital = op.dense()
    ad = jnp.asarray(a_digital, jnp.float32)
    if inner not in ("cg", "richardson"):
        raise ValueError(f"unknown inner solver {inner!r}")

    squeeze = b.ndim == 1
    bb = (b[:, None] if squeeze else b).astype(jnp.float32)
    x0b = jnp.zeros_like(bb) if x0 is None else \
        (x0[:, None] if squeeze else x0).astype(jnp.float32)
    key = jax.random.PRNGKey(0) if key is None else key

    pallas = use_pallas(backend)
    mvms_single = 0
    if inner == "cg":
        inner_core = functools.partial(
            _cg_core, op, tol=inner_tol, maxiter=inner_iters,
            use_pallas=pallas)
    else:
        if omega is None:
            # Resolve omega ONCE for the unchanged operator -- estimating it
            # inside every outer iteration would re-spend 2*iters analog MVMs
            # per correction on the same spectral bounds.
            pi_iters = 8
            lmin, lmax = spectral_bounds(
                op, key=jax.random.fold_in(key, 900_002), iters=pi_iters)
            omega = 2.0 / (1.05 * lmax + max(lmin, 0.0))
            mvms_single = 2 * pi_iters
        inner_core = functools.partial(
            _stationary_core, op, None, omega=omega, tol=inner_tol,
            maxiter=inner_iters, use_pallas=pallas, power_iters=0)

    @solver_core
    def core(b, x0, key):
        batch = b.shape[1]
        bn = jnp.maximum(col_norms(b), _TINY)
        r0 = b - ad @ x0                                 # digital, exact

        def cond(state):
            k, _x, _r, rel, _h, _m = state
            return jnp.logical_and(k < maxiter,
                                   jnp.logical_not(jnp.all(rel <= tol)))

        def body(state):
            k, x, r, _rel, hist, mvms = state
            ikey = jax.random.fold_in(key, 500_000 + k)
            out = inner_core(r, jnp.zeros_like(r), ikey)
            d, inner_mvms = out[0], out[3]
            x = x + d
            r = b - ad @ x                               # digital, exact
            rel = col_norms(r) / bn
            hist = hist.at[k].set(rel)
            return k + 1, x, r, rel, hist, mvms + inner_mvms

        rel0 = col_norms(r0) / bn
        state0 = (jnp.int32(0), x0, r0, rel0,
                  init_history(maxiter, batch), jnp.int32(0))
        k, x, _r, _rel, hist, mvms = jax.lax.while_loop(cond, body, state0)
        return x, hist, k, mvms, rel0

    x, hist, k, mvms, rel0 = dispatched(jax.jit(core), op)(bb, x0b, key)
    return pack_result(op, f"refine[{inner}]", x, hist, k, mvms, tol, squeeze,
                       mvms_single=mvms_single, rel0=rel0)
