"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (
    denoise_stencil,
    denoise_thomas,
    rram_ec_matmul,
    rram_encode_matmul,
)
from repro.kernels import ref as kref

KEY = jax.random.PRNGKey(42)


def rand(shape, dtype, i):
    return jax.random.normal(jax.random.fold_in(KEY, i), shape).astype(dtype)


@pytest.mark.parametrize("m,k,n,bm,bk,bn", [
    (8, 8, 8, 8, 8, 8),
    (16, 32, 24, 8, 8, 8),
    (32, 16, 16, 16, 16, 16),
    (8, 48, 16, 8, 16, 8),      # multi-step K accumulation
    (24, 24, 40, 8, 8, 8),
])
@pytest.mark.slow
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_encode_matmul_sweep(m, k, n, bm, bk, bn, dtype):
    x = rand((m, k), dtype, 0)
    w = rand((k, n), dtype, 1)
    eps = rand((k, n), dtype, 2)
    got = rram_encode_matmul(x, w, eps, sigma=0.13, levels=8,
                             block_m=bm, block_k=bk, block_n=bn)
    want = kref.encode_matmul_ref(x, w, eps, 0.13, 8, bk, bn)
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("levels", [4, 8, 64])
def test_encode_matmul_levels(levels):
    x = rand((16, 16), jnp.float32, 3)
    w = rand((16, 16), jnp.float32, 4)
    eps = rand((16, 16), jnp.float32, 5)
    got = rram_encode_matmul(x, w, eps, sigma=0.0, levels=levels,
                             block_m=8, block_k=8, block_n=8)
    want = kref.encode_matmul_ref(x, w, eps, 0.0, levels, 8, 8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=1e-4)


@pytest.mark.slow
@pytest.mark.parametrize("m,k,n", [(8, 8, 8), (16, 40, 24), (32, 16, 48)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ec_matmul_sweep(m, k, n, dtype):
    x = rand((m, k), dtype, 6)
    xt = x * (1 + 0.05 * rand((m, k), dtype, 7))
    w = rand((k, n), dtype, 8)
    wt = w * (1 + 0.05 * rand((k, n), dtype, 9))
    dw = (w - wt).astype(dtype)
    got = rram_ec_matmul(x, xt, wt, dw, block_m=8, block_k=8, block_n=8)
    want = kref.ec_matmul_ref(x, xt, wt, dw)
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol * 10)


def test_ec_matmul_unpadded_shapes():
    # 66x66 paper shape: wrapper pads to block multiples and slices back.
    x = rand((66, 66), jnp.float32, 10)
    xt = x * 1.01
    w = rand((66, 66), jnp.float32, 11)
    wt = w * 0.99
    got = rram_ec_matmul(x, xt, wt, w - wt, block_m=32, block_k=32, block_n=32)
    want = kref.ec_matmul_ref(x, xt, wt, w - wt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-3)


@pytest.mark.slow
@pytest.mark.parametrize("n,b,bb", [(16, 8, 8), (64, 16, 8), (128, 8, 8), (33, 5, 8)])
@pytest.mark.parametrize("lam", [1e-12, 1e-3, 0.5])
def test_thomas_sweep(n, b, bb, lam):
    p = rand((n, b), jnp.float32, 12)
    got = denoise_thomas(p, lam=lam, block_b=bb)
    want = kref.tridiag_solve_ref(p, lam)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_thomas_vs_dense_inverse():
    from repro.core.error_correction import denoise_least_square
    p = rand((48, 4), jnp.float32, 13)
    got = denoise_thomas(p, lam=1e-2, block_b=4)
    want = denoise_least_square(p, lam=1e-2, method="dense")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n,b", [(16, 8), (128, 16), (65, 3)])
@pytest.mark.parametrize("lam", [1e-12, 1e-5])
def test_stencil_sweep(n, b, lam):
    p = rand((n, b), jnp.float32, 14)
    got = denoise_stencil(p, lam=lam, block_b=8)
    want = kref.stencil_denoise_ref(p, lam)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)


def test_stencil_matches_thomas_at_tiny_lam():
    # For lam = 1e-12 the truncated Neumann series is exact to fp32.
    p = rand((96, 8), jnp.float32, 15)
    a = denoise_stencil(p, lam=1e-12, block_b=8)
    b = denoise_thomas(p, lam=1e-12, block_b=8)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("method", ["thomas", "stencil"])
@pytest.mark.parametrize("n", [64, 100])
def test_tier2_row_blocks(method, n):
    """Panels longer than one row block: the Thomas sweeps carry their row
    across blocks (both directions) and the stencil reads its halo rows
    from the neighbouring blocks."""
    from repro.kernels.tridiag import stencil_denoise, thomas_solve
    p = rand((n, 8), jnp.float32, 16)
    lam = 0.3
    if method == "thomas":
        got = thomas_solve(p, lam=lam, block_b=8, block_r=16, interpret=True)
        want = kref.tridiag_solve_ref(p, lam)
    else:
        got = stencil_denoise(p, lam=lam, block_b=8, block_r=16,
                              interpret=True)
        want = kref.stencil_denoise_ref(p, lam)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_encode_matmul_rng_inkernel_noise():
    """Single-pass encode kernel (in-kernel PRNG): on CPU the TPU interpreter
    stubs prng_random_bits to zeros, so we validate the sigma=0 exact path,
    determinism, and shapes; the noise distribution is TPU-only."""
    from repro.kernels.rram_mvm import encode_matmul_rng
    seed = jnp.array([7], jnp.int32)
    x = rand((16, 64), jnp.float32, 20)
    w = rand((64, 32), jnp.float32, 21)
    y0 = encode_matmul_rng(seed, x, w, sigma=0.0, levels=8,
                           block_m=16, block_k=32, block_n=32, interpret=True)
    want = kref.encode_matmul_ref(x, w, jnp.zeros_like(w), 0.0, 8, 32, 32)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(want),
                               rtol=2e-5, atol=1e-4)
    y1 = encode_matmul_rng(seed, x, w, sigma=0.1, levels=8,
                           block_m=16, block_k=32, block_n=32, interpret=True)
    y2 = encode_matmul_rng(seed, x, w, sigma=0.1, levels=8,
                           block_m=16, block_k=32, block_n=32, interpret=True)
    assert bool(jnp.all(y1 == y2))
