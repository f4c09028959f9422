"""Pallas TPU kernels for the iterative-solver update hot loop.

An analog iterative solve (``repro.solvers``) alternates one crossbar MVM with
a handful of vector operations.  On hardware the MVM is "free" (analog); the
digital update is the whole inner loop, so the solver vector algebra is fused
into single kernels here, next to the tier-2 solves in :mod:`tridiag`:

  * ``richardson_update``: given the analog product ``y ~= A x``, one kernel
    forms the residual ``r = b - y`` and the relaxed step
    ``x' = x + omega * r`` (the MELISO+ Richardson iteration) in one VMEM
    pass instead of three HBM round-trips.
  * ``cg_update``: the twin axpy of conjugate-gradient,
    ``x' = x + alpha p`` and ``r' = r - alpha (A p)``, with a per-RHS-column
    ``alpha`` (multi-RHS batching).

Both kernels grid over row blocks with the full RHS batch per block; scalar
coefficients travel as tiny (1, batch) operands so they may be traced values
(auto-estimated ``omega``, per-iteration ``alpha``).  Interpret mode on CPU,
Mosaic on TPU -- same convention as the other kernels in this package.

Both are pure traced calls, so they compose with the engine's scan-fused
streamed MVM: a ``solvers.cg(A_streamed, b, backend="pallas")`` iteration
body -- one scanned EC block sweep + one fused twin axpy -- lives entirely
inside the solver's single jitted ``lax.while_loop`` program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["richardson_update", "cg_update"]

DEFAULT_BLOCK_N = 256


def _richardson_kernel(x_ref, b_ref, y_ref, omega_ref, ox_ref, or_ref):
    r = b_ref[...] - y_ref[...]
    or_ref[...] = r
    ox_ref[...] = x_ref[...] + omega_ref[0, 0] * r


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def richardson_update(
    x: jnp.ndarray,
    b: jnp.ndarray,
    y: jnp.ndarray,
    omega: jnp.ndarray,
    *,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = False,
):
    """Fused Richardson step on (n, batch) panels.

    Returns ``(x + omega * (b - y), b - y)``; ``omega`` is a scalar (possibly
    traced -- the power-iteration estimate).
    """
    n, bt = x.shape
    assert n % block_n == 0, (n, block_n)
    om = jnp.reshape(omega.astype(jnp.float32), (1, 1))
    grid = (n // block_n,)
    row = pl.BlockSpec((block_n, bt), lambda i: (i, 0))
    return pl.pallas_call(
        _richardson_kernel,
        name="richardson_update",
        grid=grid,
        in_specs=[row, row, row, pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=(row, row),
        out_shape=(jax.ShapeDtypeStruct((n, bt), jnp.float32),
                   jax.ShapeDtypeStruct((n, bt), jnp.float32)),
        interpret=interpret,
    )(x.astype(jnp.float32), b.astype(jnp.float32), y.astype(jnp.float32), om)


def _cg_kernel(x_ref, r_ref, p_ref, ap_ref, alpha_ref, ox_ref, or_ref):
    a = alpha_ref[0, :][None, :]
    ox_ref[...] = x_ref[...] + a * p_ref[...]
    or_ref[...] = r_ref[...] - a * ap_ref[...]


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def cg_update(
    x: jnp.ndarray,
    r: jnp.ndarray,
    p: jnp.ndarray,
    ap: jnp.ndarray,
    alpha: jnp.ndarray,
    *,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = False,
):
    """Fused CG twin-axpy on (n, batch) panels with per-column ``alpha``.

    Returns ``(x + alpha * p, r - alpha * ap)``; ``alpha`` has shape
    ``(batch,)``.
    """
    n, bt = x.shape
    assert n % block_n == 0, (n, block_n)
    al = jnp.reshape(alpha.astype(jnp.float32), (1, bt))
    grid = (n // block_n,)
    row = pl.BlockSpec((block_n, bt), lambda i: (i, 0))
    return pl.pallas_call(
        _cg_kernel,
        name="cg_update",
        grid=grid,
        in_specs=[row, row, row, row, pl.BlockSpec((1, bt), lambda i: (0, 0))],
        out_specs=(row, row),
        out_shape=(jax.ShapeDtypeStruct((n, bt), jnp.float32),
                   jax.ShapeDtypeStruct((n, bt), jnp.float32)),
        interpret=interpret,
    )(x.astype(jnp.float32), r.astype(jnp.float32), p.astype(jnp.float32),
      ap.astype(jnp.float32), al)
