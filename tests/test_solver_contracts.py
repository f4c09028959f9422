"""Property-based solver-contract suite over ``repro.solvers.registry()``.

Four invariants, asserted for EVERY registered solver instead of per-solver
hand-rolled copies (the registry is the single source of truth -- a solver
added there is automatically held to all four):

  1. residual honesty -- the recorded ``final_residual`` tracks the
     family's residual recomputed DIGITALLY at the returned iterates:
     ``recompute <= max(slack * recorded, floor)`` and (for solvers whose
     history is not lagged one step) the reverse bound too;
  2. convergence flag -- ``converged <=> final_residual <= tol``, NaN-robust
     (a NaN residual is never "converged");
  3. iteration-0 honesty -- on trivial instances (zero RHS, exact ``x0``)
     the solver reports ``iterations == 0``, ``converged=True`` and a
     finite entry residual, with the init MVM still billed;
  4. ledger arithmetic -- ``total_energy_j`` decomposes exactly into the
     one-time write plus the four (rate x count) iteration terms, and a
     digital solve bills zero energy while still counting MVMs.

Problems are drawn by hypothesis (``tests/_hypo.py`` falls back to a
deterministic sweep on containers without it); shapes come from a small
sampled set so jit recompilation stays bounded while seeds and conditioning
vary freely.  The placement x backend parity matrix for the PR-10 solvers
(lsqr/lsmr/lanczos/lobpcg/admm) rides on ``conftest.assert_path_parity``.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypo import HAVE_HYPOTHESIS, HealthCheck, given, settings, st
from conftest import analog_cfg, assert_path_parity, make_analog

from repro import solvers
from repro.solvers import registry

KEY = jax.random.PRNGKey(0)
SPECS = {s.name: s for s in registry()}
NAMES = sorted(SPECS)
NEW_SOLVERS = ("lsqr", "lsmr", "lanczos", "lobpcg", "admm")

# Per-family run budget: enough iterations for the well-conditioned draws
# to converge, but the invariants hold either way.
RUN = {
    "linear": dict(tol=1e-5, maxiter=400),
    "lstsq": dict(tol=1e-5, maxiter=200),
    "lp": dict(tol=1e-4, maxiter=6000),
    "qp": dict(tol=1e-4, maxiter=2000),
    "eigen": dict(tol=1e-3, maxiter=32),
}

# Cross-path parity of the solution, by family.  The strict paths differ
# only in float32 reassociation (~1e-7 per MVM).  LSQR/LSMR amplify that:
# perturbing A by 1e-7 (relative) moves x by up to 6.4e-6, and a solve reads
# A ~40 times (forward + transposed), so differences add to ~4e-5.  Eigen
# solves are compared to the tolerance they are computed to (see below).
PARITY_TOL = {"lstsq": 5e-5, "eigen": RUN["eigen"]["tol"]}

_SUPPRESS = list(HealthCheck) if HAVE_HYPOTHESIS else ()


def _solve(spec, problem, a=None, **overrides):
    kw = dict(RUN[spec.family])
    kw.update(overrides)
    return spec.solve(problem["a"] if a is None else a, problem,
                      key=KEY, **kw)


def _assert_honest(spec, problem, res):
    recorded = float(res.final_residual)
    rec = spec.recompute(problem, res)
    assert math.isfinite(recorded), (spec.name, res)
    assert rec <= max(spec.slack * recorded, spec.floor), \
        f"{spec.name}: digital recompute {rec:.3e} vs recorded " \
        f"{recorded:.3e} (slack {spec.slack}, floor {spec.floor})"
    if not spec.lagged_history:
        # Non-lagged histories must not OVERSTATE the residual either.
        assert recorded <= max(spec.slack * rec, spec.floor), \
            f"{spec.name}: recorded {recorded:.3e} overstates digital " \
            f"recompute {rec:.3e}"


def _assert_flag(spec, res, tol):
    final = float(res.final_residual)
    want = math.isfinite(final) and final <= tol
    assert bool(res.converged) == want, (spec.name, final, tol, res.converged)


@pytest.mark.property
@pytest.mark.parametrize("name", NAMES)
@settings(max_examples=5, deadline=None, suppress_health_check=_SUPPRESS)
@given(seed=st.integers(0, 2**16 - 1),
       shape=st.sampled_from([(9, 1), (12, 2)]),
       cond=st.sampled_from([10.0, 200.0]))
def test_contract_residual_honesty_and_flag(name, seed, shape, cond):
    """Invariants 1 + 2 on random digital problems: the recorded residual
    is the digitally-recomputable one, and ``converged`` mirrors it."""
    spec = SPECS[name]
    n, batch = shape
    if not spec.multi_rhs:
        batch = 1
    problem = spec.make_problem(jax.random.PRNGKey(seed), n, batch, cond)
    res = _solve(spec, problem)
    _assert_honest(spec, problem, res)
    _assert_flag(spec, res, RUN[spec.family]["tol"])


@pytest.mark.property
@pytest.mark.parametrize(
    "name", [n for n in NAMES if SPECS[n].make_trivial is not None])
def test_contract_entry_honesty_zero_rhs(name):
    """Invariant 3: a solve already converged at entry (trivial instance)
    reports iterations == 0, converged, and a finite entry residual."""
    spec = SPECS[name]
    for batch in (1, 2) if spec.multi_rhs else (1,):
        problem = spec.make_trivial(8, batch)
        res = _solve(spec, problem, tol=1e-6)
        assert res.iterations == 0, (name, batch, res)
        assert res.converged, (name, batch, res)
        assert math.isfinite(float(res.final_residual)), (name, res)
        assert float(res.final_residual) <= 1e-6


def test_contract_entry_honesty_exact_x0():
    """Invariant 3, exact-``x0`` form, one solver per family that accepts a
    warm start: entry residual is already below tol, zero iterations."""
    ka = jax.random.fold_in(KEY, 21)
    a = SPECS["cg"].make_problem(ka, 12, 1)["a"]
    b = jax.random.normal(jax.random.fold_in(ka, 1), (12,), jnp.float32)
    res = solvers.cg(a, b, x0=jnp.linalg.solve(a, b), tol=1e-5, maxiter=50)
    assert res.iterations == 0 and res.converged

    r = SPECS["lsqr"].make_problem(jax.random.fold_in(KEY, 22), 8, 1)
    x_ls = jnp.linalg.lstsq(r["a"], r["b"])[0]
    for fn in (solvers.lsqr, solvers.lsmr):
        res = fn(r["a"], r["b"], x0=x_ls, tol=1e-4, maxiter=50)
        assert res.iterations == 0 and res.converged, (fn.__name__, res)

    qp = SPECS["admm"].make_problem(jax.random.fold_in(KEY, 23), 12, 1)
    res = solvers.admm(qp["a"], qp["b"], qp["q"], lo=qp["lo"], hi=qp["hi"],
                       x0=qp["x_star"], tol=1e-4, maxiter=200)
    assert res.iterations == 0 and res.converged, res


def test_contract_entry_analog_zero_rhs():
    """Analog zero-RHS entry convergence still bills the one init MVM."""
    a = SPECS["cg"].make_problem(jax.random.fold_in(KEY, 24), 12, 1)["a"]
    a = a + 2.0 * jnp.eye(12)
    _, A = make_analog(a)
    res = solvers.cg(A, jnp.zeros((12,)), tol=1e-6, maxiter=50)
    assert res.iterations == 0 and res.converged, res
    assert res.ledger.mvms == 1


@pytest.mark.property
@pytest.mark.parametrize("name", NAMES)
def test_contract_ledger_arithmetic(name):
    """Invariant 4: on an analog operator the total energy is EXACTLY
    write + sum of the four (MVM count x per-call rate) products; on the
    digital operator the same counts bill zero energy."""
    spec = SPECS[name]
    problem = spec.make_problem(jax.random.PRNGKey(3), 9, 1)
    _, A = make_analog(problem["a"])
    res = _solve(spec, problem, a=A)
    led = res.ledger
    counts = (led.mvms, led.mvms_single, led.mvms_t, led.mvms_single_t)
    assert all(c >= 0 for c in counts) and sum(counts) >= 1, (name, counts)
    assert led.write_energy_j > 0
    assert led.total_energy_j == pytest.approx(
        led.write_energy_j
        + led.mvms * float(led.input_stats.energy_j)
        + led.mvms_single * float(led.input_stats_single.energy_j)
        + led.mvms_t * float(led.input_stats_t.energy_j)
        + led.mvms_single_t * float(led.input_stats_single_t.energy_j))
    if spec.needs_rmatvec:
        assert led.mvms_t + led.mvms_single_t >= 1, (name, counts)
        assert float(led.input_stats_t.energy_j) > 0

    res_d = _solve(spec, problem)
    led_d = res_d.ledger
    assert led_d.total_energy_j == 0.0    # digital operator: free MVMs...
    assert led_d.mvms + led_d.mvms_single >= 1  # ...still counted


# ------------------------------------------------- placement x backend matrix
@pytest.mark.parametrize("name", NEW_SOLVERS)
def test_new_solver_path_parity_matrix(name):
    """The PR-10 solvers run draw-identically (<= 1e-5, same iteration
    count) across the placement x backend matrix: dense local handle,
    streamed producer, streamed pallas tile-step and the distributed 1x1
    mesh.  The resident=False virtual producer re-derives its blocks
    in-scan (reassociated float32 math, ~1e-7 per MVM), which compounds
    over a full solve's recurrences: it matches at 1e-3 with iteration
    drift allowed."""
    spec = SPECS[name]
    problem = spec.make_problem(jax.random.PRNGKey(5), 12, 1)
    cfg = analog_cfg(problem["a"].shape[0])

    def run(engine, A):
        res = _solve(spec, problem, a=A, maxiter=min(
            RUN[spec.family]["maxiter"], 300))
        out = {"x": res.x, "it": jnp.float32(res.iterations)}
        if res.dual is not None:
            out["dual"] = res.dual
        if res.eigenvalues is not None:
            out["eig"] = res.eigenvalues
        return out

    from conftest import run_paths
    results = run_paths(problem["a"], cfg, run, key=KEY,
                        paths=("local", "streamed", "pallas", "dist-1x1",
                               "virtual"))
    drop = {"it"}
    if spec.family == "eigen":
        # Ritz VECTORS are only pinned down to ~residual/gap at the solve
        # tolerance, so cross-path vector comparison is not the invariant.
        # Instead every path's vectors must pass the digital Ritz residual,
        # and the eigenVALUES must agree to the solve tolerance: the paths
        # differ only in float32 reassociation, but perturbing A by 1e-7
        # (relative) already moves these eigenvalues by ~9e-5, so no bound
        # tighter than the tolerance they are computed to is reasoned.
        a_d = problem["a"]
        for path, r in results.items():
            resid = jnp.linalg.norm(a_d @ r["x"] - r["x"] * r["eig"][None, :],
                                    axis=0)
            assert float(jnp.max(resid / jnp.abs(r["eig"]))) <= 5e-3, path
        drop = {"it", "x"}
    for p in ("streamed", "pallas", "dist-1x1"):
        # iteration counts across strictly-scheduled paths are EQUAL
        assert float(results[p]["it"]) == float(results["local"]["it"]), p
    strict = {p: {k: v for k, v in r.items() if k not in drop}
              for p, r in results.items() if p != "virtual"}
    assert_path_parity(strict, reference="local",
                       tol=PARITY_TOL.get(spec.family, 1e-5))
    loose = {p: {k: v for k, v in results[p].items() if k not in drop}
             for p in ("dist-1x1", "virtual")}
    assert_path_parity(loose, reference="dist-1x1", tol=1e-3)
