"""The plain reference itself: its CG meets the configuration's guarantee
against the exact product, and its precisions order as they should."""
import jax
import jax.numpy as jnp
import pytest

import meliso_reference as ref
from conftest import small_cell


@pytest.mark.parametrize("name,tol,maxiter", [
    ("paper-cg", 5e-3, 12), ("resident-cg", 1e-3, 40)])
def test_reference_cg_true_residual(name, tol, maxiter):
    conf = small_cell(name).config
    r = ref.Reference(conf, jax.random.PRNGKey(1))
    b = jax.random.normal(jax.random.PRNGKey(2), (conf["n"], 1))
    x, iters, mvms = r.cg(b, jax.random.PRNGKey(3), tol=tol, maxiter=maxiter)
    true = jnp.linalg.norm(ref.exact_mvm(r.spec, x) - b) / jnp.linalg.norm(b)
    assert 1 <= iters < maxiter and mvms == iters + 1
    assert float(true) <= 10 * tol


def test_corrected_mvm_error_and_high_precision_gap():
    conf = small_cell("resident-mvm-b64").config
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(jax.random.PRNGKey(2), (conf["n"], 8))
    ys = {p: ref.Reference(conf, key, p).mvm(x, jax.random.PRNGKey(3))
          for p in ref.PRECISIONS}
    exact = ref.exact_mvm(ref.Spec.from_config(conf), x)
    err = lambda y: float(jnp.linalg.norm(y - exact) / jnp.linalg.norm(exact))
    # epiram with two-tier EC: the paper's ~4e-4 relative error.
    assert 2e-4 < err(ys["highest"]) < 1e-3
    gap = float(jnp.max(jnp.abs(ys["high"] - ys["highest"])))
    # three bf16 passes keep ~16 bits of each operand: a gap well under the
    # EC error, and not nothing.
    assert 0 < gap < 0.1 * err(ys["highest"]) * float(jnp.max(jnp.abs(exact)))


def test_zero_input_gives_zero():
    conf = small_cell("paper-cg").config
    r = ref.Reference(conf, jax.random.PRNGKey(1))
    y = r.mvm(jnp.zeros((conf["n"], 1)), jax.random.PRNGKey(4))
    assert float(jnp.max(jnp.abs(y))) == 0.0
