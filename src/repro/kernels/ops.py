"""Jit'd public wrappers around the Pallas kernels.

On a TPU the kernels lower through Mosaic.  On the CPU backend (the test
suite, run with ``JAX_PLATFORMS=cpu``) they execute in interpret mode -- the
kernel body runs as traced JAX ops per grid point, validating the exact TPU
dataflow; :func:`on_cpu` is the only place that mode is chosen.  The wrappers
handle padding to block multiples and un-padding, so callers pass natural
shapes.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from .rram_mvm import DEFAULT_BLOCK_K, DEFAULT_BLOCK_M, DEFAULT_BLOCK_N
from .rram_mvm import DEFAULT_MATVEC_BLOCK_K, DEFAULT_MATVEC_BLOCK_M
from .rram_mvm import DEFAULT_PACKED_BLOCK_K, DEFAULT_PACKED_BLOCK_M
from .rram_mvm import PACKED_MAX_COLS
from .rram_mvm import ec_matmul as _ec_matmul
from .rram_mvm import ec_matmul_packed as _ec_matmul_packed
from .rram_mvm import ec_matvec as _ec_matvec
from .rram_mvm import encode_matmul as _encode_matmul
from .rram_mvm import matrix_shape
from .solver_update import cg_update as _cg_update
from .solver_update import richardson_update as _richardson_update
from .tridiag import stencil_denoise as _stencil
from .tridiag import thomas_solve as _thomas

__all__ = [
    "on_cpu",
    "tier1_form",
    "rram_encode_matmul",
    "rram_ec_matmul",
    "rram_ec_tile_mvm",
    "rram_ec_tile_rmvm",
    "rram_ec_group_mvm",
    "rram_ec_group_rmvm",
    "denoise_thomas",
    "denoise_stencil",
    "solver_richardson_update",
    "solver_cg_update",
]


def on_cpu() -> bool:
    """Whether the kernels must run interpreted (the CPU backend)."""
    return jax.default_backend() == "cpu"


def _pad_to(x: jnp.ndarray, mults) -> jnp.ndarray:
    pads = []
    for dim, mult in zip(x.shape, mults):
        pads.append((0, (-dim) % mult))
    if all(p == (0, 0) for p in pads):
        return x
    return jnp.pad(x, pads)


def _pick_blocks(m, k, n, bm, bk, bn):
    """Shrink default blocks for small problems (keeps interpret tests fast and
    avoids padding a 66x66 paper matrix to 512^2)."""
    return min(bm, max(8, m)), min(bk, max(8, k)), min(bn, max(8, n))


def _fit(block: int, cap: int) -> int:
    """A tile extent that divides the capacity-block extent ``cap``."""
    return block if cap % block == 0 else cap


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def tier1_form(cols: int, transposed: bool = False) -> str:
    """The tier-1 form :func:`rram_ec_matmul` takes for a corrected MVM of
    ``cols`` input columns with the image on the left (a forward MVM):
    ``"vpu"`` (the VPU form) for one column, ``"mxu_packed"`` (the input
    split once and packed two parts to a 128-lane panel) for 2-64 columns,
    ``"mxu"`` (two f32 ``HIGHEST`` MXU dots per block) for more.  A
    transposed MVM puts the input on the left and the image on the right,
    whose columns are the image's, so it keeps the MXU form."""
    if transposed or cols > PACKED_MAX_COLS:
        return "mxu"
    return "vpu" if cols == 1 else "mxu_packed"


def rram_encode_matmul(
    x: jnp.ndarray,
    w: jnp.ndarray,
    eps: jnp.ndarray,
    *,
    sigma: float,
    levels: int,
    block_m: int = DEFAULT_BLOCK_M,
    block_k: int = DEFAULT_BLOCK_K,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """y = x @ encode(w); per-(block_k, block_n) tile = one MCA array."""
    m, k = x.shape
    _, n = w.shape
    bm, bk, bn = _pick_blocks(m, k, n, block_m, block_k, block_n)
    xp = _pad_to(x, (bm, bk))
    wp = _pad_to(w, (bk, bn))
    ep = _pad_to(eps, (bk, bn))
    out = _encode_matmul(
        xp, wp, ep, sigma=sigma, levels=levels,
        block_m=bm, block_k=bk, block_n=bn,
        interpret=on_cpu() if interpret is None else interpret)
    return out[:m, :n]


def rram_ec_matmul(
    l1: jnp.ndarray,
    l2: jnp.ndarray,
    r1: jnp.ndarray,
    r2: jnp.ndarray,
    *,
    block_m: int | None = None,
    block_k: int | None = None,
    block_n: int | None = None,
    transposed: bool = False,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Fused tier-1 EC matmul p = l1 @ r1 + l2 @ r2.

    Either operand pair may be a (mb, nb, cap_m, cap_n) capacity-block stack
    (read in place, tiles chosen to divide the capacity block); 2-D operands
    are padded to the tile grid and the result is un-padded.  ``transposed``
    says the image pair is on the right (the transposed MVM).  The form
    follows the right-hand pair's column count and the direction
    (:func:`tier1_form`): one column runs the VPU form (``ec_matvec``, tiles
    ``block_m`` x ``block_k``, default ``DEFAULT_MATVEC_BLOCK_*``,
    ``block_n`` unused), 2-64 the packed MXU form (``ec_matmul_packed``,
    default ``DEFAULT_PACKED_BLOCK_*``, ``block_n`` unused), any other the
    MXU form (``ec_matmul``, default ``DEFAULT_BLOCK_*``).
    """
    m, k = matrix_shape(l1)
    _, n = matrix_shape(r1)
    interpret = on_cpu() if interpret is None else interpret
    form = tier1_form(n, transposed)
    with jax.named_scope("meliso.tier1"):
        if form == "vpu":
            out = _tier1_vpu(l1, l2, r1, r2, m, k,
                             block_m or DEFAULT_MATVEC_BLOCK_M,
                             block_k or DEFAULT_MATVEC_BLOCK_K, interpret)
            return out[:m]
        if form == "mxu_packed":
            out = _tier1_packed(l1, l2, r1, r2, m, k,
                                block_m or DEFAULT_PACKED_BLOCK_M,
                                block_k or DEFAULT_PACKED_BLOCK_K, interpret)
            return out[:m]
        bm, bk, bn = _pick_blocks(m, k, n, block_m or DEFAULT_BLOCK_M,
                                  block_k or DEFAULT_BLOCK_K,
                                  block_n or DEFAULT_BLOCK_N)
        if l1.ndim == 4:
            bm, bk = _fit(bm, l1.shape[2]), _fit(bk, l1.shape[3])
        if r1.ndim == 4:
            bk, bn = _fit(bk, r1.shape[2]), _fit(bn, r1.shape[3])
        left = [a if a.ndim == 4 else _pad_to(a, (bm, bk)) for a in (l1, l2)]
        right = [a if a.ndim == 4 else _pad_to(a, (bk, bn))
                 for a in (r1, r2)]
        out = _ec_matmul(*left, *right, block_m=bm, block_k=bk, block_n=bn,
                         interpret=interpret)
        return out[:m, :n]


def _image_tiles(l1, l2, m, k, bm, bk):
    """The image pair as the VPU and packed forms read it, with their
    (bm, bk) tile: a capacity-block stack in place, tiles fit to its block;
    a 2-D pair padded to tiles no larger than it (rounded up to (8, 128))."""
    if l1.ndim == 4:
        return [l1, l2], _fit(bm, l1.shape[2]), _fit(bk, l1.shape[3])
    bm = min(bm, _round_up(m, 8))
    bk = min(bk, _round_up(k, 128))
    return [_pad_to(a, (bm, bk)) for a in (l1, l2)], bm, bk


def _tier1_vpu(l1, l2, r1, r2, m, k, bm, bk, interpret):
    """The VPU form of :func:`rram_ec_matmul` for a one-column right pair:
    the column becomes a lane-major (1, k) row, tiles fit the capacity block
    or the (padded) matrix.  Returns (m_padded, 1)."""
    left, bm, bk = _image_tiles(l1, l2, m, k, bm, bk)
    kp = matrix_shape(left[0])[1]
    rows = [_pad_to(r.reshape(1, k), (1, kp)) for r in (r1, r2)]
    return _ec_matvec(*left, *rows, block_m=bm, block_k=bk,
                      interpret=interpret)


def _tier1_packed(l1, l2, r1, r2, m, k, bm, bk, interpret):
    """The packed MXU form of :func:`rram_ec_matmul` for a 2-64-column right
    pair: tiles fit the capacity block or the (padded) matrix, the input is
    padded to the image's K.  Returns (m_padded, cols)."""
    left, bm, bk = _image_tiles(l1, l2, m, k, bm, bk)
    kp = matrix_shape(left[0])[1]
    right = [_pad_to(r, (kp, 1)) for r in (r1, r2)]
    return _ec_matmul_packed(*left, *right, block_m=bm, block_k=bk,
                             interpret=interpret)


def rram_ec_tile_mvm(
    x_blk: jnp.ndarray,
    x_t: jnp.ndarray,
    at_blk: jnp.ndarray,
    da_blk: jnp.ndarray,
    *,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Tier-1 EC step for ONE capacity tile in the engine's (n, batch) layout.

    Computes ``at_blk @ x_blk + da_blk @ x_t`` as a single fused
    :func:`rram_ec_matmul` call with the tile on the left, so the streamed
    scan body and the host-loop fallback share one kernel-backed tile step
    and no operand is transposed.  ``x_blk``/``x_t``: (cap_n, batch);
    ``at_blk``/``da_blk``: (cap_m, cap_n).  Returns fp32 (cap_m, batch).
    """
    return rram_ec_matmul(at_blk, da_blk, x_blk, x_t, interpret=interpret)


def rram_ec_tile_rmvm(
    y_blk: jnp.ndarray,
    y_t: jnp.ndarray,
    at_blk: jnp.ndarray,
    da_blk: jnp.ndarray,
    *,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """TRANSPOSED tier-1 EC step for ONE capacity tile ((m, batch) layout).

    Computes ``at_blk.T @ y_blk + da_blk.T @ y_t`` as a single fused
    :func:`rram_ec_matmul` call -- the ``z^T = y^T At + y_t^T dA`` form, i.e.
    the same kernel read in the transposed direction, so the transposed
    streamed scan body and the host-loop fallback share one kernel-backed
    tile step; only the (cap_m, batch) input panels are transposed.
    ``y_blk``/``y_t``: (cap_m, batch); ``at_blk``/``da_blk``:
    (cap_m, cap_n).  Returns fp32 (cap_n, batch).
    """
    with jax.named_scope("meliso.tier1"):
        return rram_ec_matmul(y_blk.T, y_t.T, at_blk, da_blk,
                              transposed=True, interpret=interpret).T


def rram_ec_group_mvm(
    x_g: jnp.ndarray,
    x_t_g: jnp.ndarray,
    at_g: jnp.ndarray,
    da_g: jnp.ndarray,
    *,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Tier-1 EC step for a STACK of images under an extra leading image axis.

    All operands carry a leading group axis ``g``: ``x_g``/``x_t_g`` are
    (g, n, batch) input panels, ``at_g``/``da_g`` (g, m, n) dense operands.
    Runs the fused :func:`rram_ec_matmul` kernel once per member inside a
    single ``lax.map`` (a scan -- ONE traced program, the kernel grid never
    sees the image axis), returning (g, m, batch).  Member ``g`` is
    bit-identical to a solo :func:`rram_ec_tile_mvm` on its slice.
    """
    def one(ops):
        x, x_t, at, da = ops
        return rram_ec_tile_mvm(x, x_t, at, da, interpret=interpret)

    with jax.named_scope("meliso.tier1"):
        return jax.lax.map(one, (x_g, x_t_g, at_g, da_g))


def rram_ec_group_rmvm(
    y_g: jnp.ndarray,
    y_t_g: jnp.ndarray,
    at_g: jnp.ndarray,
    da_g: jnp.ndarray,
    *,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """TRANSPOSED grouped tier-1 EC step: the :func:`rram_ec_tile_rmvm`
    mirror of :func:`rram_ec_group_mvm`.  ``y_g``/``y_t_g`` are (g, m, batch),
    ``at_g``/``da_g`` (g, m, n); returns (g, n, batch) -- the same kernel read
    backwards per member under one ``lax.map``."""
    def one(ops):
        y, y_t, at, da = ops
        return rram_ec_tile_rmvm(y, y_t, at, da, interpret=interpret)

    with jax.named_scope("meliso.tier1"):
        return jax.lax.map(one, (y_g, y_t_g, at_g, da_g))


def solver_richardson_update(
    x: jnp.ndarray, b: jnp.ndarray, y: jnp.ndarray, omega,
    *, block_n: int = 256, interpret: bool | None = None,
):
    """Fused solver step (x + omega*(b - y), b - y) for (n, batch) panels."""
    n, bt = x.shape
    bn = min(block_n, max(1, n))
    pad = (-n) % bn
    xp, bp, yp = (_pad_to(a, (bn, 1)) for a in (x, b, y))
    xn, r = _richardson_update(
        xp, bp, yp, jnp.asarray(omega), block_n=bn,
        interpret=on_cpu() if interpret is None else interpret)
    return (xn[:n], r[:n]) if pad else (xn, r)


def solver_cg_update(
    x: jnp.ndarray, r: jnp.ndarray, p: jnp.ndarray, ap: jnp.ndarray, alpha,
    *, block_n: int = 256, interpret: bool | None = None,
):
    """Fused CG twin-axpy (x + alpha*p, r - alpha*ap), alpha per RHS column."""
    n, bt = x.shape
    bn = min(block_n, max(1, n))
    pad = (-n) % bn
    xp, rp, pp, app = (_pad_to(a, (bn, 1)) for a in (x, r, p, ap))
    xn, rn = _cg_update(
        xp, rp, pp, app, jnp.asarray(alpha), block_n=bn,
        interpret=on_cpu() if interpret is None else interpret)
    return (xn[:n], rn[:n]) if pad else (xn, rn)


def denoise_thomas(
    p: jnp.ndarray, *, lam: float, h: float = -1.0,
    block_b: int = 128, interpret: bool | None = None,
) -> jnp.ndarray:
    """Exact tier-2 solve for (n, batch) panels."""
    n, b = p.shape
    bb = min(block_b, max(1, b))
    with jax.named_scope("meliso.tier2"):
        pp = _pad_to(p, (1, bb))
        out = _thomas(pp, lam=lam, h=h, block_b=bb,
                      interpret=on_cpu() if interpret is None else interpret)
        return out[:, :b]


def denoise_stencil(
    p: jnp.ndarray, *, lam: float, h: float = -1.0,
    block_b: int = 128, interpret: bool | None = None,
) -> jnp.ndarray:
    """Truncated-Neumann tier-2 denoise for (n, batch) panels."""
    n, b = p.shape
    bb = min(block_b, max(1, b))
    with jax.named_scope("meliso.tier2"):
        pp = _pad_to(p, (1, bb))
        out = _stencil(pp, lam=lam, h=h, block_b=bb,
                       interpret=on_cpu() if interpret is None else interpret)
        return out[:, :b]
