"""Pallas TPU kernels for the RRAM crossbar MVM simulation.

Two kernels, both tiled so that one (block_k x block_n) weight tile == one MCA
array: the VMEM tile *is* the crossbar, and the grid iteration over K-blocks is
the virtualization reassignment loop (DESIGN.md section 2).

  * ``encode_matmul``: y = x_tilde @ W_tilde with the encode (per-tile
    conductance quantization + programming noise) computed **in-VMEM**, so the
    encoded weights never round-trip to HBM.  This is the analog-simulation
    fast path: one HBM read of W instead of (write W_tilde + read W_tilde).

  * ``ec_matmul``: the two-tier-EC serving path.  Computes the fused tier-1
    combination p = l1 @ r1 + l2 @ r2 -- the image pair (A_tilde, dA) on one
    side, the input pair (x, x_tilde) on the other -- issuing two MXU dots per
    block, 33% fewer FLOPs than the paper's three analog products.  Either
    pair may be a (mb, nb, cap_m, cap_n) capacity-block stack, read in place
    as the matrix it tiles, so a programmed image is never transposed or
    re-assembled into a dense copy.

Block shapes default to (512, 512) weight tiles (the paper's best-performing
MCA cell size, conveniently 4x the 128x128 MXU tile) and 256-row activation
panels; fp32 accumulation in the output ref across the K grid dimension.

Every f32 dot runs at the simulation's matmul precision,
:data:`repro.core.crossbar.PRECISION`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.crossbar import PRECISION

__all__ = ["encode_matmul", "ec_matmul"]

DEFAULT_BLOCK_M = 256
DEFAULT_BLOCK_K = 512   # MCA cell rows (contraction)
DEFAULT_BLOCK_N = 512   # MCA cell cols (output features)

_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


# --------------------------------------------------------------------------- #
# encode_matmul: on-the-fly encode + matmul
# --------------------------------------------------------------------------- #

def _encode_matmul_kernel(x_ref, w_ref, eps_ref, o_ref, *, sigma, levels, nsteps):
    """One (bm, bn) output block, accumulating over the K grid axis.

    The (bk, bn) weight tile in VMEM is one MCA: quantize with the tile's own
    conductance scale, apply programming noise, then one MXU dot.
    """
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    w = w_ref[...].astype(jnp.float32)
    scale = jnp.max(jnp.abs(w))
    scale = jnp.where(scale == 0.0, 1.0, scale)
    q = jnp.round(w / scale * (levels - 1)) / (levels - 1) * scale
    w_tilde = q * (1.0 + sigma * eps_ref[...].astype(jnp.float32))
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] += jnp.dot(x, w_tilde, preferred_element_type=jnp.float32,
                          precision=PRECISION)


@functools.partial(
    jax.jit,
    static_argnames=("sigma", "levels", "block_m", "block_k", "block_n", "interpret"),
)
def encode_matmul(
    x: jnp.ndarray,
    w: jnp.ndarray,
    eps: jnp.ndarray,
    *,
    sigma: float,
    levels: int,
    block_m: int = DEFAULT_BLOCK_M,
    block_k: int = DEFAULT_BLOCK_K,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = False,
) -> jnp.ndarray:
    """y = x @ (Q(w) * (1 + sigma * eps)) with per-(block_k, block_n)-tile Q.

    x: (m, k); w, eps: (k, n).  m, k, n must be multiples of the block shape
    (the ops wrapper pads).  Returns fp32 (m, n).
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2 and eps.shape == w.shape, (x.shape, w.shape, eps.shape)
    assert m % block_m == 0 and k % block_k == 0 and n % block_n == 0, (
        (m, k, n), (block_m, block_k, block_n))
    grid = (m // block_m, n // block_n, k // block_k)
    return pl.pallas_call(
        functools.partial(
            _encode_matmul_kernel, sigma=sigma, levels=levels, nsteps=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, s: (i, s)),
            pl.BlockSpec((block_k, block_n), lambda i, j, s: (s, j)),
            pl.BlockSpec((block_k, block_n), lambda i, j, s: (s, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=_PARAMS,
        interpret=interpret,
    )(x, w, eps)


# --------------------------------------------------------------------------- #
# encode_matmul_rng: encode + matmul with IN-KERNEL noise generation
# --------------------------------------------------------------------------- #

def _encode_matmul_rng_kernel(seed_ref, x_ref, w_ref, o_ref, *, sigma, levels):
    """Like _encode_matmul_kernel but the programming noise is drawn inside
    the kernel (pltpu PRNG seeded per tile + Box-Muller), so the eps array
    never exists in HBM: the weight tile is read exactly once per MCA
    assignment -- the single-pass analog-simulation path (EXPERIMENTS.md M3).
    """
    i, j, s_ = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(s_ == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    w = w_ref[...].astype(jnp.float32)
    scale = jnp.max(jnp.abs(w))
    scale = jnp.where(scale == 0.0, 1.0, scale)
    q = jnp.round(w / scale * (levels - 1)) / (levels - 1) * scale

    # Mosaic seeds from at most two words: the seed and the linear tile id.
    tile = (i * pl.num_programs(1) + j) * pl.num_programs(2) + s_
    pltpu.prng_seed(seed_ref[0], tile)
    # Two uniform draws -> Box-Muller standard normal.
    bits1 = pltpu.prng_random_bits(w.shape)
    bits2 = pltpu.prng_random_bits(w.shape)
    # Top 24 bits as a non-negative int32 (Mosaic casts int32, not uint32,
    # to float32).
    u1 = jax.lax.shift_right_logical(bits1, 8).astype(jnp.float32) / (1 << 24)
    u2 = jax.lax.shift_right_logical(bits2, 8).astype(jnp.float32) / (1 << 24)
    u1 = jnp.maximum(u1, 1e-7)
    eta = jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(2.0 * jnp.pi * u2)

    w_tilde = q * (1.0 + sigma * eta)
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] += jnp.dot(x, w_tilde, preferred_element_type=jnp.float32,
                          precision=PRECISION)


@functools.partial(
    jax.jit,
    static_argnames=("sigma", "levels", "block_m", "block_k", "block_n",
                     "interpret"),
)
def encode_matmul_rng(
    seed: jnp.ndarray,
    x: jnp.ndarray,
    w: jnp.ndarray,
    *,
    sigma: float,
    levels: int,
    block_m: int = DEFAULT_BLOCK_M,
    block_k: int = DEFAULT_BLOCK_K,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = False,
) -> jnp.ndarray:
    """y = x @ encode(w) with in-VMEM noise: W is the only O(k*n) HBM read.

    Validation caveat (DESIGN.md section 7): the CPU TPU-interpreter stubs
    ``prng_random_bits`` to zeros, so only the sigma=0 path (exact per-tile
    quantized matmul) and determinism are checkable off-TPU; the Box-Muller
    noise path exercises real hardware PRNG.  ``interpret=True`` runs the
    TPU interpreter (``pltpu.InterpretParams()``), which lowers the PRNG
    primitives on CPU.
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2
    assert m % block_m == 0 and k % block_k == 0 and n % block_n == 0
    grid = (m // block_m, n // block_n, k // block_k)
    if interpret is True:
        interpret = pltpu.InterpretParams()
    return pl.pallas_call(
        functools.partial(_encode_matmul_rng_kernel, sigma=sigma, levels=levels),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block_m, block_k), lambda i, j, s: (i, s)),
            pl.BlockSpec((block_k, block_n), lambda i, j, s: (s, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=_PARAMS,
        interpret=interpret,
    )(seed, x, w)


# --------------------------------------------------------------------------- #
# ec_matmul: fused tier-1 error-corrected matmul
# --------------------------------------------------------------------------- #

def _ec_matmul_kernel(l1_ref, l2_ref, r1_ref, r2_ref, o_ref):
    """p_block = l1 @ r1 + l2 @ r2, fp32 accumulation over the K grid axis."""
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    f32 = jnp.float32
    acc = jnp.dot(l1_ref[...].astype(f32), r1_ref[...].astype(f32),
                  preferred_element_type=f32, precision=PRECISION)
    acc += jnp.dot(l2_ref[...].astype(f32), r2_ref[...].astype(f32),
                   preferred_element_type=f32, precision=PRECISION)
    o_ref[...] += acc


def matrix_shape(a: jnp.ndarray):
    """(rows, cols) of a 2-D operand or of the matrix a capacity-block stack
    (mb, nb, cap_m, cap_n) tiles."""
    if a.ndim == 2:
        return a.shape
    mb, nb, cm, cn = a.shape
    return mb * cm, nb * cn


def _tile_spec(a: jnp.ndarray, block, index):
    """BlockSpec of one ``block`` tile of the matrix ``a`` holds.

    ``index`` maps the grid point to the tile's (row, col) block index in the
    matrix; for a capacity-block stack it is split into the capacity block
    and the tile inside it (``block`` must divide the capacity block).
    """
    if a.ndim == 2:
        return pl.BlockSpec(block, index)
    _, _, cm, cn = a.shape
    assert cm % block[0] == 0 and cn % block[1] == 0, (a.shape, block)
    per_m, per_n = cm // block[0], cn // block[1]

    def stacked(*g):
        r, c = index(*g)
        return r // per_m, c // per_n, r % per_m, c % per_n

    return pl.BlockSpec((pl.squeezed, pl.squeezed) + tuple(block), stacked)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_k", "block_n", "interpret"))
def ec_matmul(
    l1: jnp.ndarray,
    l2: jnp.ndarray,
    r1: jnp.ndarray,
    r2: jnp.ndarray,
    *,
    block_m: int = DEFAULT_BLOCK_M,
    block_k: int = DEFAULT_BLOCK_K,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused tier-1 EC product p = l1 @ r1 + l2 @ r2.

    ``l1``, ``l2``: (m, k); ``r1``, ``r2``: (k, n); either pair may instead
    be a capacity-block stack of that matrix (see :func:`matrix_shape`).
    With the input pair on the left, (x^T, x_tilde^T) against
    (A_tilde^T, dA^T), it is the paper's x @ W_tilde + x_tilde @ dW; with the
    image pair on the left it is A_tilde x + dA x_tilde.  Returns fp32 (m, n).
    """
    m, k = matrix_shape(l1)
    _, n = matrix_shape(r1)
    assert l2.shape == l1.shape and r2.shape == r1.shape
    assert m % block_m == 0 and k % block_k == 0 and n % block_n == 0, (
        (m, k, n), (block_m, block_k, block_n))
    grid = (m // block_m, n // block_n, k // block_k)
    left = [_tile_spec(a, (block_m, block_k), lambda i, j, s: (i, s))
            for a in (l1, l2)]
    right = [_tile_spec(a, (block_k, block_n), lambda i, j, s: (s, j))
             for a in (r1, r2)]
    return pl.pallas_call(
        _ec_matmul_kernel,
        grid=grid,
        in_specs=left + right,
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=_PARAMS,
        interpret=interpret,
    )(l1, l2, r1, r2)
