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
from repro.core import crossbar
from repro.kernels import ref as kref
from repro.kernels.ops import tier1_form
from repro.kernels.rram_mvm import split_bf16

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


def _block_stack(a, cap_m, cap_n):
    """The (mb, nb, cap_m, cap_n) capacity-block stack of the matrix ``a``."""
    m, k = a.shape
    return a.reshape(m // cap_m, cap_m, k // cap_n, cap_n).transpose(0, 2, 1, 3)


@pytest.mark.slow
@pytest.mark.parametrize("m,k,n", [
    (8, 8, 8), (16, 40, 24), (32, 16, 48),
    (24, 256, 1),   # one column: the VPU form, K over two tiles
    (20, 300, 1),   # the VPU form at padded m and k
    (32, 512, 1),   # the VPU form, also reading a capacity-block stack
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ec_matmul_sweep(m, k, n, dtype):
    x = rand((m, k), dtype, 6)
    xt = x * (1 + 0.05 * rand((m, k), dtype, 7))
    w = rand((k, n), dtype, 8)
    wt = w * (1 + 0.05 * rand((k, n), dtype, 9))
    dw = (w - wt).astype(dtype)
    block_k = 128 if n == 1 else 8
    got = rram_ec_matmul(x, xt, wt, dw, block_m=8, block_k=block_k, block_n=8)
    want = kref.ec_matmul_ref(x, xt, wt, dw)
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol * 10)
    if n == 1 and m % 16 == 0 and k % 256 == 0:
        got = rram_ec_matmul(_block_stack(x, 16, 256), _block_stack(xt, 16, 256),
                             wt, dw, block_m=8, block_k=128)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=tol, atol=tol * 10)


def _kernel_grids(fn, *args):
    """(grid, operand count) of every pallas_call ``fn`` traces to."""
    grids = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                grids.append((eqn.params["grid_mapping"].grid,
                              len(eqn.invars)))
            for p in eqn.params.values():
                inner = getattr(p, "jaxpr", p)
                if hasattr(inner, "eqns"):
                    walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return grids


# Each form's pallas_call: (grid rank, operands).  The packed form reads the
# image pair and four input panels.
_FORM_CALL = {"vpu": (2, 4), "mxu_packed": (2, 6), "mxu": (3, 4)}


@pytest.mark.parametrize("cols,transposed,form", [
    (1, False, "vpu"), (1, True, "mxu"), (2, False, "mxu_packed"),
    (64, False, "mxu_packed"), (64, True, "mxu"), (65, False, "mxu"),
    (128, False, "mxu")])
def test_tier1_form(cols, transposed, form):
    """The VPU form is taken for one column with the image on the left, the
    packed MXU form for 2-64, the MXU form beyond and for every transposed
    MVM, and the wrapper runs the form the predicate names: a 2-D (rows, K)
    grid for the VPU and packed forms, a 3-D one for the MXU form."""
    assert tier1_form(cols, transposed) == form
    image = rand((32, 256), jnp.float32, 17)
    if transposed:
        y = rand((32, cols), jnp.float32, 18)
        call = lambda a, v: rram_ec_matmul(v.T, v.T, a, a, transposed=True)
    else:
        y = rand((256, cols), jnp.float32, 18)
        call = lambda a, v: rram_ec_matmul(a, a, v, v)
    grids = _kernel_grids(call, image, y)
    assert grids and all((len(g), ops) == _FORM_CALL[form]
                         for g, ops in grids), grids


def _gap(got, want):
    """Largest entry gap over the largest entry of ``want`` (float64)."""
    want = np.asarray(want, np.float64)
    return np.abs(np.asarray(got, np.float64) - want).max() / np.abs(want).max()


def _image_and_input(m, k, cols, seed):
    """An image pair (A_tilde, dA ~1e-3 of it) and an input pair (x and its
    DAC image ~1% off), f32."""
    a = rand((m, k), jnp.float32, seed)
    d = 1e-3 * rand((m, k), jnp.float32, seed + 1)
    x = rand((k, cols), jnp.float32, seed + 2)
    xt = x * (1 + 0.01 * rand((k, cols), jnp.float32, seed + 3))
    return a, d, x, xt


@pytest.mark.parametrize("cols", [2, 33, 64])
@pytest.mark.parametrize("layout", ["2d", "stack", "padded"])
def test_ec_matmul_packed(cols, layout):
    """The packed form equals the f32 product crossbar.matmul forms at
    HIGHEST within f32 rounding, on a 2-D image, a capacity-block stack
    read in place (two row bands, two K steps each) and a 2-D image padded
    to its tiles."""
    m, k = (40, 300) if layout == "padded" else (64, 512)
    a, d, x, xt = _image_and_input(m, k, cols, 20)
    want = crossbar.matmul(a, x) + crossbar.matmul(d, xt)
    if layout == "stack":
        a, d = _block_stack(a, 32, 256), _block_stack(d, 32, 256)
    got = rram_ec_matmul(a, d, x, xt, block_m=32, block_k=128)
    assert got.shape == (m, cols)
    assert _gap(got, want) < 2e-6


def test_split_bf16_exact():
    """The three bf16 parts of f32 values over 50 decades, both signs and
    zero, sum to the value exactly, each part at most half a bf16 unit of
    the one before."""
    rng = np.random.default_rng(3)
    a = rng.standard_normal(8192) * 10.0 ** rng.uniform(-25, 25, 8192)
    a = jnp.asarray(np.append(a, [0.0, -0.0, 1.0, -3.0]), jnp.float32)
    parts = split_bf16(a)
    assert all(p.dtype == jnp.bfloat16 for p in parts)
    a1, a2, a3 = (p.astype(jnp.float32) for p in parts)
    np.testing.assert_array_equal(np.asarray(a1 + a2 + a3), np.asarray(a))
    assert bool(jnp.all(jnp.abs(a2) <= jnp.abs(a1) * 2.0 ** -8))
    assert bool(jnp.all(jnp.abs(a3) <= jnp.abs(a2) * 2.0 ** -8))


def test_ec_matmul_packed_precision():
    """The packed form keeps all six bf16 products of HIGHEST: its gap to
    the float64 product is at the f32 product's level and far under that of
    HIGH's three (a1x1 + a1x2 + a2x1), so dropping any one of the six
    fails."""
    a, d, x, xt = _image_and_input(64, 2048, 64, 30)
    f64 = lambda t: np.asarray(t, np.float64)
    exact = f64(a) @ f64(x) + f64(d) @ f64(xt)

    def high(l, r):
        l1, l2, _ = (f64(p.astype(jnp.float32)) for p in split_bf16(l))
        r1, r2, _ = (f64(p.astype(jnp.float32)) for p in split_bf16(r))
        return l1 @ r1 + l1 @ r2 + l2 @ r1

    packed = _gap(rram_ec_matmul(a, d, x, xt, block_m=32, block_k=512),
                  exact)
    f32 = _gap(crossbar.matmul(a, x) + crossbar.matmul(d, xt), exact)
    assert packed <= f32, (packed, f32)
    assert _gap(high(a, x) + high(d, xt), exact) > 10 * packed


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
