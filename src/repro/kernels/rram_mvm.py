"""Pallas TPU kernels for the RRAM crossbar MVM simulation.

The MXU kernels are tiled so that one (block_k x block_n) weight tile == one
MCA array: the VMEM tile *is* the crossbar, and the grid iteration over
K-blocks is the virtualization reassignment loop (DESIGN.md section 2).

  * ``encode_matmul``: y = x_tilde @ W_tilde with the encode (per-tile
    conductance quantization + programming noise) computed **in-VMEM**, so the
    encoded weights never round-trip to HBM.  This is the analog-simulation
    fast path: one HBM read of W instead of (write W_tilde + read W_tilde).

  * the fused tier-1 EC product p = l1 @ r1 + l2 @ r2 of the serving path --
    the image pair (A_tilde, dA) on one side, the input pair (x, x_tilde) on
    the other, 33% fewer FLOPs than the paper's three analog products.
    Either pair may be a (mb, nb, cap_m, cap_n) capacity-block stack, read in
    place as the matrix it tiles, so a programmed image is never transposed
    or re-assembled into a dense copy.  It has three forms, each one
    ``pallas_call`` named ``ec_matmul``:

      - ``ec_matmul`` (the MXU form): two f32 MXU dots per block at
        ``HIGHEST``, six bf16 passes each.  It serves a right-hand pair of
        more than 64 columns, and the transposed MVM, whose right-hand pair
        is the image itself.
      - ``ec_matmul_packed`` (the packed MXU form): a right-hand pair of 2-64
        input columns against the image on the left.  The input pair is
        split into bf16 parts once per call and packed two parts to a
        128-lane panel, so the six bf16 products of ``HIGHEST`` take four
        MXU passes, not six passes on half-empty panels; each image tile is
        split in VMEM, and the panels are read once per 2,048-row band.
      - ``ec_matvec`` (the VPU form): a right-hand pair of ONE column, i.e. a
        batch-1 forward MVM with the image on the left (every solver
        iteration).  An MXU pass would pad that column to a 128-wide panel
        and spend several bf16 passes per f32 product on it; here each image
        tile is multiplied elementwise by the input, held as a lane-major
        (1, k) row, and folded into a lane-wide f32 accumulator with VPU adds,
        so the product streams the image at HBM speed.

    :func:`repro.kernels.ops.rram_ec_matmul` picks the form from the shape
    (:func:`repro.kernels.ops.tier1_form`).

Block shapes default to (512, 512) weight tiles (the paper's best-performing
MCA cell size, conveniently 4x the 128x128 MXU tile) and 256-row activation
panels; fp32 accumulation in the output ref across the K grid dimension.  The
VPU and packed forms read larger (DEFAULT_MATVEC_BLOCK_*, DEFAULT_PACKED_*)
image tiles, chosen by sweeps on a TPU v5e (PERF.md).

Every f32 dot runs at the simulation's matmul precision,
:data:`repro.core.crossbar.PRECISION`; the VPU form's products are full f32
multiplies and the packed form's are the six bf16 products ``HIGHEST``
forms, so both are as exact (only the order of summation differs).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.crossbar import PRECISION

__all__ = ["encode_matmul", "ec_matmul", "ec_matvec", "ec_matmul_packed",
           "split_bf16"]

DEFAULT_BLOCK_M = 256
DEFAULT_BLOCK_K = 512   # MCA cell rows (contraction)
DEFAULT_BLOCK_N = 512   # MCA cell cols (output features)
DEFAULT_MATVEC_BLOCK_M = 1024   # image rows per VPU-form tile
DEFAULT_MATVEC_BLOCK_K = 2048   # image cols (contraction) per VPU-form tile
DEFAULT_PACKED_BLOCK_M = 2048   # image rows per packed-form tile (a row band)
DEFAULT_PACKED_BLOCK_K = 1024   # image cols (contraction) per packed-form tile
DEFAULT_PACKED_STRIP_ROWS = 512  # rows split and multiplied at a time

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
        name="encode_matmul",
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
        name="encode_matmul_rng",
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


_LANES = 128
_STRIP_ROWS = 32   # rows of the partial sum one strip keeps in registers
_VMEM_SLACK = 4 << 20   # room for Mosaic's own scratch


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
    """Fused tier-1 EC product p = l1 @ r1 + l2 @ r2, two f32 dots per block.

    ``l1``, ``l2``: (m, k); ``r1``, ``r2``: (k, n); either pair may instead
    be a capacity-block stack of that matrix (see :func:`matrix_shape`).
    With the input pair on the left, (x^T, x_tilde^T) against
    (A_tilde^T, dA^T), it is the paper's x @ W_tilde + x_tilde @ dW; with the
    image pair on the left it is A_tilde x + dA x_tilde.  Each dot runs six
    bf16 passes (``HIGHEST``) over a right-hand tile of ``block_n`` columns,
    so a pair of up to 64 input columns is better served by
    :func:`ec_matmul_packed`.  Returns fp32 (m, n).
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
        name="ec_matmul",
        grid=grid,
        in_specs=left + right,
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=_PARAMS,
        interpret=interpret,
    )(l1, l2, r1, r2)


# --------------------------------------------------------------------------- #
# ec_matvec: the single-column fused tier-1 product on the VPU
# --------------------------------------------------------------------------- #



def _ec_matvec_kernel(l1_ref, l2_ref, r1_ref, r2_ref, o_ref, acc_ref, *,
                      rows):
    """One (bm, 1) output block of l1 @ r1 + l2 @ r2 for a single column.

    ``r1``/``r2`` are (1, bk) lane-major rows.  Strip by strip of ``rows``
    rows, the (rows, bk) image tiles are multiplied elementwise by the
    broadcast rows and their bk / w lane chunks folded into a (rows, w) f32
    partial sum, added to the (bm, w) accumulator in VMEM scratch: VPU
    multiplies and adds only.  The last K step reduces across the lanes.
    """
    k_step = pl.program_id(1)
    bm, bk = l1_ref.shape
    w = acc_ref.shape[1]
    f32 = jnp.float32

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def strip(i, carry):
        r = pl.ds(pl.multiple_of(i * rows, rows), rows)
        acc = acc_ref[r, :]
        for c in range(bk // w):
            cols = pl.ds(c * w, w)
            acc += l1_ref[r, cols].astype(f32) * r1_ref[:, cols].astype(f32)
            acc += l2_ref[r, cols].astype(f32) * r2_ref[:, cols].astype(f32)
        acc_ref[r, :] = acc
        return carry

    jax.lax.fori_loop(0, bm // rows, strip, 0)

    @pl.when(k_step == pl.num_programs(1) - 1)
    def _out():
        o_ref[...] = jnp.sum(acc_ref[...], axis=1, keepdims=True)


def _matvec_vmem_bytes(block_m: int, block_k: int, lanes: int,
                       itemsize: int) -> int:
    """VMEM the VPU form's tiles take: two buffers of each image tile, of
    each (1, bk) row and of the (bm, 1) output (both padded to (8, 128)
    tiles), the accumulator, and Mosaic's slack."""
    rows_bytes = 2 * 8 * max(block_k, _LANES) * 4
    blocks = 2 * block_m * block_k * itemsize + rows_bytes \
        + block_m * _LANES * 4
    return 2 * blocks + block_m * lanes * 4 + _VMEM_SLACK


@functools.partial(jax.jit,
                   static_argnames=("block_m", "block_k", "interpret"))
def ec_matvec(
    l1: jnp.ndarray,
    l2: jnp.ndarray,
    r1: jnp.ndarray,
    r2: jnp.ndarray,
    *,
    block_m: int = DEFAULT_MATVEC_BLOCK_M,
    block_k: int = DEFAULT_MATVEC_BLOCK_K,
    interpret: bool = False,
) -> jnp.ndarray:
    """Single-column fused tier-1 EC product p = l1 @ r1 + l2 @ r2, on the VPU.

    ``l1``, ``l2``: (m, k) or a capacity-block stack of that matrix (the
    image pair, read in place); ``r1``, ``r2``: (1, k), the input column and
    its DAC image as lane-major rows.  Each product is a full f32 multiply and
    the sums are f32, as exact as :func:`ec_matmul` at ``HIGHEST``; only the
    order of summation differs.  Returns fp32 (m, 1).
    """
    m, k = matrix_shape(l1)
    assert l2.shape == l1.shape and r1.shape == r2.shape == (1, k), (
        l1.shape, l2.shape, r1.shape, r2.shape)
    assert m % block_m == 0 and k % block_k == 0, ((m, k), (block_m, block_k))
    lanes = _LANES if block_k % _LANES == 0 else block_k
    grid = (m // block_m, k // block_k)
    left = [_tile_spec(a, (block_m, block_k), lambda i, s: (i, s))
            for a in (l1, l2)]
    row = pl.BlockSpec((1, block_k), lambda i, s: (0, s))
    vmem = _matvec_vmem_bytes(block_m, block_k, lanes, l1.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_ec_matvec_kernel,
                          rows=math.gcd(block_m, _STRIP_ROWS)),
        name="ec_matmul",
        grid=grid,
        in_specs=left + [row, row],
        out_specs=pl.BlockSpec((block_m, 1), lambda i, s: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_m, lanes), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
    )(l1, l2, r1, r2)


# --------------------------------------------------------------------------- #
# ec_matmul_packed: 2-64 columns on the MXU, the input split once per call
# --------------------------------------------------------------------------- #

PACKED_MAX_COLS = _LANES // 2   # two bf16 input parts per 128-lane panel
_HI_BITS = -(1 << 16)           # 0xFFFF0000: sign, exponent, 7 mantissa bits
_HALF_LO = 1 << 15              # half a bf16 unit in the last place


def split_bf16(a: jnp.ndarray):
    """Three bf16 parts whose f32 sum is ``a`` (f32) exactly.

    Each part is what the parts before it leave, rounded to bf16 half away
    from zero by integer ops on its bits (add half a unit, mask the low 16
    bits): its remainder is at most half a unit, so an f32 significand of 24
    bits leaves at most 8 for the third part, which bf16 holds, and the
    remainders' signs are not tied to the value's as a truncation's are.
    Integer ops cost the VPU less than round-to-nearest-even, and no
    compiler can fold them away as it may a convert to bf16 and back
    (PERF.md, PR 17).  Values under 2^-103 (~1e-31), whose last part may be
    subnormal, are not split exactly where subnormals are flushed.
    """
    def head(v):
        bits = jax.lax.bitcast_convert_type(v, jnp.int32)
        return jax.lax.bitcast_convert_type((bits + _HALF_LO) & _HI_BITS,
                                            jnp.float32)

    a = a.astype(jnp.float32)
    a1 = head(a)
    r = a - a1
    a2 = head(r)
    return tuple(p.astype(jnp.bfloat16) for p in (a1, a2, r - a2))


def _pack_panels(r: jnp.ndarray):
    """The (k, c) input, c <= 64, as the packed form's two (k, 128) bf16
    panels ``[r1 | r2]`` and ``[r3 | 0]`` of its :func:`split_bf16` parts."""
    k, c = r.shape
    assert 1 <= c <= PACKED_MAX_COLS, r.shape
    r1, r2, r3 = split_bf16(r)
    zeros = functools.partial(jnp.zeros, dtype=jnp.bfloat16)
    return (jnp.concatenate([r1, r2, zeros((k, _LANES - 2 * c))], axis=1),
            jnp.concatenate([r3, zeros((k, _LANES - c))], axis=1))


def _ec_packed_kernel(l1_ref, l2_ref, p1_ref, q1_ref, p2_ref, q2_ref, o_ref,
                      hi_ref, lo_ref, *, rows):
    """One (bm, c) output block of l1 @ r1 + l2 @ r2 from packed panels.

    Strip by strip of ``rows`` rows, each f32 image tile is split into bf16
    parts a1 + a2 + a3 and multiplied by its input's panels P = [x1 | x2]
    and Q = [x3 | 0] in four single-pass MXU products that hold the six
    bf16 products HIGHEST forms: ``hi`` gathers a1 P + a2 P
    (a1x1 + a2x1 | a1x2 + a2x2), ``lo`` a3 P + a1 Q (a3x1 + a1x3 | a3x2,
    never read).  The last K step folds ``hi``'s two lane halves and
    ``lo``'s first.
    """
    k_step = pl.program_id(1)
    bm, c = o_ref.shape
    f32 = jnp.float32

    @pl.when(k_step == 0)
    def _init():
        hi_ref[...] = jnp.zeros_like(hi_ref)
        lo_ref[...] = jnp.zeros_like(lo_ref)

    def dot(a, b):
        return jnp.dot(a, b, preferred_element_type=f32)

    def strip(i, carry):
        r = pl.ds(pl.multiple_of(i * rows, rows), rows)
        hi, lo = hi_ref[r, :], lo_ref[r, :]
        for l_ref, p_ref, q_ref in ((l1_ref, p1_ref, q1_ref),
                                    (l2_ref, p2_ref, q2_ref)):
            a1, a2, a3 = split_bf16(l_ref[r, :])
            p = p_ref[...]
            hi += dot(a1, p) + dot(a2, p)
            lo += dot(a3, p) + dot(a1, q_ref[...])
        hi_ref[r, :] = hi
        lo_ref[r, :] = lo
        return carry

    jax.lax.fori_loop(0, bm // rows, strip, 0)

    @pl.when(k_step == pl.num_programs(1) - 1)
    def _out():
        hi = hi_ref[...]
        # Lane j < c of the roll holds hi's lane j + c: the x2 half.
        folded = hi + pltpu.roll(hi, _LANES - c, 1) + lo_ref[...]
        o_ref[...] = folded[:, :c]


def _packed_vmem_bytes(block_m: int, block_k: int, rows: int,
                       itemsize: int) -> int:
    """VMEM the packed form takes: two buffers of each image tile, of each
    (bk, 128) bf16 panel and of the (bm, c) output (lane-padded), the two
    accumulators, one strip's split parts and products, and Mosaic's
    slack."""
    blocks = 2 * block_m * block_k * itemsize + 4 * block_k * _LANES * 2 \
        + block_m * _LANES * 4
    strip = rows * block_k * (4 + 4 + 3 * 2) + 4 * rows * _LANES * 4
    return 2 * blocks + 2 * block_m * _LANES * 4 + strip + _VMEM_SLACK


@functools.partial(jax.jit,
                   static_argnames=("block_m", "block_k", "interpret"))
def ec_matmul_packed(
    l1: jnp.ndarray,
    l2: jnp.ndarray,
    r1: jnp.ndarray,
    r2: jnp.ndarray,
    *,
    block_m: int = DEFAULT_PACKED_BLOCK_M,
    block_k: int = DEFAULT_PACKED_BLOCK_K,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused tier-1 EC product p = l1 @ r1 + l2 @ r2 for 2-64 columns.

    ``l1``, ``l2``: (m, k) or a capacity-block stack of that matrix (the
    image pair, read in place); ``r1``, ``r2``: (k, c), c <= 64, the input
    and its DAC image.  The inputs are split into bf16 parts and packed
    (:func:`_pack_panels`) once per call, outside the grid; each image tile
    is split in VMEM.  The six bf16 products per f32 product are those of
    ``HIGHEST``, in four MXU passes instead of six (the c columns would fill
    half a pass), and the panels are read once per ``block_m`` rows of the
    image.  Only the order of summation differs from :func:`ec_matmul`.
    Returns fp32 (m, c).
    """
    m, k = matrix_shape(l1)
    c = r1.shape[1]
    assert l2.shape == l1.shape and r1.shape == r2.shape == (k, c), (
        l1.shape, l2.shape, r1.shape, r2.shape)
    assert m % block_m == 0 and k % block_k == 0, ((m, k), (block_m, block_k))
    rows = math.gcd(block_m, DEFAULT_PACKED_STRIP_ROWS)
    grid = (m // block_m, k // block_k)
    left = [_tile_spec(a, (block_m, block_k), lambda i, s: (i, s))
            for a in (l1, l2)]
    panel = pl.BlockSpec((block_k, _LANES), lambda i, s: (s, 0))
    panels = [p for r in (r1, r2) for p in _pack_panels(r)]
    vmem = _packed_vmem_bytes(block_m, block_k, rows, l1.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_ec_packed_kernel, rows=rows),
        name="ec_matmul",
        grid=grid,
        in_specs=left + [panel] * 4,
        out_specs=pl.BlockSpec((block_m, c), lambda i, s: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, c), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_m, _LANES), jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
    )(l1, l2, *panels)
