"""Plain reference of the MELISO+ corrected MVM and of CG.

Written from the configuration alone, in plain ``jax.numpy`` at float32 and
the precision the caller names; it imports nothing of the program under
test and takes nothing the program has made.  It follows the same seeded
key discipline as the simulation it checks, so a sound program and this
reference draw the same programming and input-DAC noise and agree to
float32 rounding:

- the per-block keys are ``split(key, mb * nb)`` over the global block grid;
  each splits into ``(k_a, k_x)``: ``k_a`` draws the programming noise of
  the block's tiles, ``k_x`` the DAC noise of its input chunk;
- a resident image is programmed once, from the programming key's block
  keys; a ``resident=False`` image is re-encoded inside every MVM from the
  call key's block keys (nothing of A is ever held);
- the input DAC draws either per block (``dac_draw: "per_block"``, the
  ``k_x`` of the call key's block keys) or once over the whole input
  (``"whole_input"``, from ``fold_in(call_key, 1)``: the fused kernel's
  path);
- CG's MVM keys are ``fold_in(solve_key, 0)`` for the initial residual and
  ``fold_in(solve_key, 1 + k)`` for iteration ``k``.

The product of a programmed block with its input is the fused tier-1 EC
``A_tilde x + dA x_tilde`` with ``dA = A - A_tilde``; tier-2 is the
two-term Neumann smoothing ``p - lam K p`` with ``K = L^T L``.

Everything here runs block by block, so it fits beside nothing else on the
chip once the program's state is freed.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

_TINY = 1e-30
PRECISIONS = ("highest", "high")


@dataclasses.dataclass(frozen=True)
class Spec:
    """The numbers of a configuration that the simulation depends on."""

    n: int
    capacity: int
    cell_rows: int
    cell_cols: int
    levels: int
    sigma: float
    lam: float
    h: float
    matrix_seed: int
    bandwidth: int
    diag: float
    texture: float
    texture_reach: int
    resident: bool
    dac_draw: str

    @classmethod
    def from_config(cls, conf: dict) -> "Spec":
        dev, mat = conf["device"], conf["matrix"]
        return cls(n=conf["n"], capacity=conf["capacity"],
                   cell_rows=conf["cell_rows"], cell_cols=conf["cell_cols"],
                   levels=dev["levels"], sigma=write_sigma(dev, conf["k_iters"]),
                   lam=conf["lam"], h=conf["h"], matrix_seed=mat["seed"],
                   bandwidth=mat["bandwidth"], diag=mat["diag"],
                   texture=mat["texture"], texture_reach=mat["texture_reach"],
                   resident=conf["resident"], dac_draw=conf["dac_draw"])

    @property
    def blocks(self) -> int:
        return self.n // self.capacity


def write_sigma(dev: dict, k_iters: int) -> float:
    """Relative programming noise after ``k_iters`` write-verify passes:
    ``sigma0 (1 - gain)^k`` with the verify gain cut by the device's mean
    nonlinearity, floored at the quantization noise ``1 / (levels sqrt 12)``."""
    nl = 0.5 * (abs(dev["nl_pot"]) + abs(dev["nl_dep"]))
    gain = dev["verify_gain"] / (1.0 + 0.35 * nl)
    sigma = dev["sigma0"] * (1.0 - gain) ** k_iters
    return max(sigma, 1.0 / (dev["levels"] * math.sqrt(12.0)))


def banded_block(spec: Spec, i, j) -> jnp.ndarray:
    """Block (i, j) of the configuration's matrix: a band of ``1 / (1 + d)``
    for ``d <= bandwidth``, ``diag`` more on the diagonal, and a seeded
    normal texture of scale ``texture`` within ``texture_reach`` of it.
    Handed to the program as its ``block_fn``, and read by the oracle."""
    cap = spec.capacity
    key = jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(spec.matrix_seed), i), j)
    blk = spec.texture * jax.random.normal(key, (cap, cap), jnp.float32)
    rows = i * cap + jnp.arange(cap)[:, None]
    cols = j * cap + jnp.arange(cap)[None, :]
    dist = jnp.abs(rows - cols)
    band = jnp.where(dist <= spec.bandwidth,
                     1.0 / (1.0 + dist.astype(jnp.float32)), 0.0)
    blk = blk * (dist <= spec.texture_reach) + band
    blk = blk + spec.diag * (rows == cols)
    valid = (rows < spec.n) & (cols < spec.n)
    return jnp.where(valid, blk, 0.0)


def dense_matrix(spec: Spec) -> jnp.ndarray:
    """The whole (n, n) matrix, assembled from its blocks in one program."""
    nb, cap = spec.blocks, spec.capacity

    def row(i):
        return jax.lax.map(lambda j: banded_block(spec, i, j), jnp.arange(nb))

    blocks = jax.lax.map(row, jnp.arange(nb))          # (nb, nb, cap, cap)
    return blocks.transpose(0, 2, 1, 3).reshape(nb * cap, nb * cap)


def quantize(w, levels: int, axis):
    """Symmetric quantization to ``levels`` states per polarity, scaled by
    the max-abs over ``axis``."""
    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = jnp.where(scale == 0.0, 1.0, scale)
    return jnp.round(w / scale * (levels - 1)) / (levels - 1) * scale


def encode_block(spec: Spec, a, key):
    """Programmed image of one capacity block: each (cell_rows, cell_cols)
    tile quantized with its own range, times ``1 + sigma * eta``."""
    m, n = a.shape
    r, c = spec.cell_rows, spec.cell_cols
    tiles = a.reshape(m // r, r, n // c, c)
    q = quantize(tiles, spec.levels, (1, 3))
    eta = jax.random.normal(key, tiles.shape, jnp.float32)
    return (q * (1.0 + jnp.float32(spec.sigma) * eta)).reshape(m, n)


def encode_input(spec: Spec, x, key):
    """Input-DAC image of an (n, batch) panel, one range per column."""
    q = quantize(x, spec.levels, 0)
    eta = jax.random.normal(key, x.shape, jnp.float32)
    return q * (1.0 + jnp.float32(spec.sigma) * eta)


def denoise(spec: Spec, p):
    """Tier-2: ``p - lam K p``, ``(K v)_i = (1 + h^2) v_i + h (v_{i-1} +
    v_{i+1})`` with row 0's diagonal 1."""
    h = spec.h
    up = jnp.concatenate([p[1:], jnp.zeros_like(p[:1])], axis=0)
    dn = jnp.concatenate([jnp.zeros_like(p[:1]), p[:-1]], axis=0)
    kp = (1.0 + h * h) * p + h * (up + dn)
    kp = kp.at[0].add(-(h * h) * p[0])
    return p + (-spec.lam) * kp


def _bf16(v):
    """``v`` rounded to bfloat16, kept in float32.  ``reduce_precision`` is
    an explicit rounding the compiler keeps (a float32 -> bfloat16 ->
    float32 round trip may be elided as excess precision)."""
    return jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)


def matmul(a, b, precision: str):
    """``a @ b`` with float32 accumulation, its operands held at
    ``precision``: ``highest`` is float32; ``high`` is three bfloat16 passes
    (``hi hi + hi lo + lo hi``, the split a TPU's ``Precision.HIGH`` makes),
    spelled out so that it reads the same on every backend."""
    dot = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    if precision == "highest":
        return dot(a, b)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}; one of "
                         f"{PRECISIONS}")
    a_hi, b_hi = _bf16(a), _bf16(b)
    a_lo, b_lo = _bf16(a - a_hi), _bf16(b - b_hi)
    return dot(a_hi, b_hi) + (dot(a_hi, b_lo) + dot(a_lo, b_hi))


def block_keys(key, nb: int):
    keys = jax.random.split(key, nb * nb)
    return keys.reshape((nb, nb) + keys.shape[1:])


@functools.partial(jax.jit, static_argnames=("spec", "precision"))
def corrected_mvm(spec: Spec, x, call_key, program_key, *, precision: str):
    """Corrected MVM ``A x`` of an (n, batch) panel, block row by block row."""
    nb, cap = spec.blocks, spec.capacity
    batch = x.shape[1]
    xc = x.reshape(nb, cap, batch)
    call_keys = block_keys(call_key, nb)
    image_keys = block_keys(program_key, nb) if spec.resident else call_keys
    if spec.dac_draw == "whole_input":
        xtc = encode_input(spec, x, jax.random.fold_in(call_key, 1)
                           ).reshape(nb, cap, batch)
    elif spec.dac_draw != "per_block":
        raise ValueError(f"unknown dac_draw {spec.dac_draw!r}")

    def row(i):
        def col(acc, j):
            a = banded_block(spec, i, j)
            k_a, _ = jax.random.split(image_keys[i, j])
            at = encode_block(spec, a, k_a)
            if spec.dac_draw == "whole_input":
                xt = xtc[j]
            else:
                _, k_x = jax.random.split(call_keys[i, j])
                xt = encode_input(spec, xc[j], k_x)
            prod = (matmul(at, xc[j], precision)
                    + matmul(a - at, xt, precision))
            return acc + prod, None

        acc, _ = jax.lax.scan(col, jnp.zeros((cap, batch), jnp.float32),
                              jnp.arange(nb))
        return acc

    p = jax.lax.map(row, jnp.arange(nb)).reshape(nb * cap, batch)
    return denoise(spec, p)


@functools.partial(jax.jit, static_argnames=("spec",))
def exact_mvm(spec: Spec, x):
    """The digital product ``A x`` at HIGHEST, block by block (the oracle
    of true residuals)."""
    nb, cap = spec.blocks, spec.capacity
    xc = x.reshape(nb, cap, x.shape[1])
    hi = jax.lax.Precision.HIGHEST

    def row(i):
        def col(acc, j):
            return acc + jnp.matmul(banded_block(spec, i, j), xc[j],
                                    precision=hi), None

        acc, _ = jax.lax.scan(col, jnp.zeros(xc.shape[1:], jnp.float32),
                              jnp.arange(nb))
        return acc

    return jax.lax.map(row, jnp.arange(nb)).reshape(x.shape)


class Reference:
    """The configuration's corrected MVMs and CG solves, at ``precision``."""

    def __init__(self, conf: dict, program_key, precision: str = "highest"):
        self.spec = Spec.from_config(conf)
        self.program_key = program_key
        self.precision = precision

    def mvm(self, x, key):
        return corrected_mvm(self.spec, x, key, self.program_key,
                             precision=self.precision)

    def solve(self, b, key, *, solver: str, tol: float, maxiter: int):
        if solver != "cg":
            raise ValueError(f"no {solver!r} solve in the reference")
        return self.cg(b, key, tol=tol, maxiter=maxiter)

    def free(self) -> None:
        """Nothing to free: the reference holds no image (it stands in the
        program's place for the control)."""

    def cg(self, b, key, *, tol: float, maxiter: int):
        """CG from x0 = 0; returns ``(x, iterations, mvms)``.  The initial
        residual is ``b`` itself: a zero input quantizes to zero, so its
        corrected product is exactly zero."""
        x = jnp.zeros_like(b)
        r = b
        p = r
        rho = jnp.sum(r * r, axis=0)
        bn = jnp.maximum(jnp.sqrt(jnp.sum(b * b, axis=0)), _TINY)
        rel = jnp.sqrt(rho) / bn
        k = 0
        while k < maxiter and not bool(jnp.all(rel <= tol)):
            ap = self.mvm(p, jax.random.fold_in(key, 1 + k))
            alpha = rho / jnp.maximum(jnp.sum(p * ap, axis=0), _TINY)
            x = x + alpha[None, :] * p
            r = r - alpha[None, :] * ap
            rho_new = jnp.sum(r * r, axis=0)
            beta = rho_new / jnp.maximum(rho, _TINY)
            p = r + beta[None, :] * p
            rel = jnp.sqrt(rho_new) / bn
            rho = rho_new
            k += 1
        return x, k, k + 1
