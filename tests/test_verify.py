"""Verifier-on-the-verifier: every pass must flag its known-bad program.

Covers the ISSUE acceptance criteria: for each of the five passes a
deliberately broken pipeline (materializing MVM, double-dispatch loop,
duplicated key, f16 accumulator, stray all-gather) that the pass must
flag; attribution-message snapshots proving violations name the
offending primitive and source line; regression tests for the traversal
gaps the seed walker had (custom_vjp fwd thunk, dict/nested params);
and the registry wiring `tools/check_invariants.py` gates on.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import verify as V
from repro.analysis.memory import jaxpr_max_elements, max_aval_elements

KEY = jax.random.PRNGKey(3)


# ------------------------------------------------------- walker regressions
class TestWalkerRegressions:
    """Sub-jaxprs the seed walker could not reach must now be walked."""

    def test_custom_vjp_fwd_thunk_reached(self):
        """A big residual allocated in a custom_vjp fwd rule is invisible
        in a primal-only trace except through ``fwd_jaxpr_thunk`` -- the
        one-level param scan of the seed walker returned 8 here."""
        @jax.custom_vjp
        def f(x):
            return jnp.sum(x)

        def fwd(x):
            big = jnp.zeros((1024, 1024)) + x[0]      # hidden residual
            return jnp.sum(x), jnp.sum(big)

        def bwd(res, g):
            return (jnp.ones((8,)) * g * res,)

        f.defvjp(fwd, bwd)
        jx = jax.make_jaxpr(f)(jnp.ones((8,)))
        assert [e.primitive.name for e in jx.jaxpr.eqns] == \
            ["custom_vjp_call"]                 # primal-only: un-inlined
        assert jaxpr_max_elements(jx) == 1024 * 1024

    @staticmethod
    def _rewritten(params_patch):
        """A real jaxpr whose jit eqn hides its sub-jaxpr per ``patch``."""
        def inner(x):
            return jnp.sin(jnp.outer(x, x)).sum()

        outer = jax.make_jaxpr(jax.jit(inner))(jnp.ones((128,)))
        eqn = outer.jaxpr.eqns[0]
        sub = eqn.params["jaxpr"]
        params = {k: v for k, v in eqn.params.items() if k != "jaxpr"}
        params.update(params_patch(sub))
        new_eqn = eqn.replace(params=params)
        return outer.jaxpr.replace(
            eqns=[new_eqn] + list(outer.jaxpr.eqns[1:]))

    def test_dict_valued_params_walked(self):
        jx = self._rewritten(lambda sub: {"branch_map": {"a": sub}})
        assert jaxpr_max_elements(jx) == 128 * 128

    def test_nested_container_params_walked(self):
        jx = self._rewritten(lambda sub: {"nested": ((("deep", sub),),)})
        assert jaxpr_max_elements(jx) == 128 * 128

    def test_cond_branches_walked(self):
        def f(x, p):
            return jax.lax.cond(p > 0,
                                lambda v: jnp.outer(v, v).sum(),
                                lambda v: jnp.sum(v), x)
        assert max_aval_elements(f, jnp.ones((64,)), jnp.float32(1)) == 64 * 64


# ----------------------------------------------------------- AvalBound
def _materializing_mvm(x):
    """The known-bad memory pipeline: forms the full rank-1 'matrix'."""
    big = jnp.outer(x, x)
    return big @ x


class TestAvalBound:
    def test_flags_materializing_mvm(self):
        jx = V.trace(_materializing_mvm, jnp.ones((512,)))
        report = V.aval_bound(jx, budget=1024)
        assert not report.ok
        assert report.summary["max_elements"] == 512 * 512
        assert report.summary["max_aval"] == "float32[512,512]"

    def test_attribution_names_primitive_and_line(self):
        jx = V.trace(_materializing_mvm, jnp.ones((512,)))
        msg = str(V.aval_bound(jx, budget=1024).violations[0])
        assert re.search(
            r"AvalBound: largest aval float32\[512,512\] has 262144 "
            r"elements > budget 1024 "
            r"\[\w+ @ test_verify\.py:\d+ \(in _materializing_mvm\)\]", msg), msg

    def test_clean_under_budget(self):
        jx = V.trace(lambda x: (x * 2).sum(), jnp.ones((512,)))
        assert V.aval_bound(jx, budget=512).ok

    def test_assert_ok_raises_with_sites(self):
        jx = V.trace(_materializing_mvm, jnp.ones((512,)))
        with pytest.raises(AssertionError, match="AvalBound failed"):
            V.aval_bound(jx, budget=1024).assert_ok()


# ----------------------------------------------------------- DispatchCount
class TestDispatchCount:
    def test_flags_double_dispatch_loop(self):
        """The known-bad dispatch pipeline: one jitted dispatch per step
        instead of one fused scan."""
        def chained(x):
            for _ in range(4):                        # 4 top-level dispatches
                x = jax.jit(jnp.sin)(x)
            return x

        report = V.dispatch_count(V.trace(chained, jnp.ones((8,))),
                                  max_top_level=1)
        assert not report.ok
        assert report.summary["top_level_eqns"] == 4
        assert report.summary["per_primitive"] == {"jit": 4}
        assert "4 top-level equations > budget 1" in str(report.violations[0])

    def test_single_fused_dispatch_clean(self):
        def fused(x):
            return jax.jit(lambda v: jnp.cos(jnp.sin(v)))(x)

        report = V.dispatch_count(V.trace(fused, jnp.ones((8,))),
                                  max_top_level=1)
        assert report.ok
        assert report.summary["dispatch_boundaries"] == 1

    def test_flags_producer_overcall(self):
        counter = V.CallCounter(lambda i, j: jnp.ones((4, 4)))
        for i in range(5):
            counter(i, 0)                              # per-block re-invocation
        report = V.dispatch_count(V.trace(lambda x: x + 1, jnp.ones((2,))),
                                  producer_calls=counter.calls,
                                  max_producer_calls=3)
        assert not report.ok
        assert "producer invoked 5x" in str(report.violations[0])


# ----------------------------------------------------------- KeyReuse
class TestKeyReuse:
    def test_flags_duplicated_key(self):
        """The known-bad key pipeline: two draws from the same key."""
        def bad(key, x):
            return (jax.random.normal(key, x.shape)
                    + jax.random.normal(key, x.shape) + x)

        report = V.key_reuse(V.trace(bad, KEY, jnp.ones((4,))))
        assert not report.ok
        assert report.summary["consumptions"] == 2
        assert report.summary["distinct_keys"] == 1
        assert "identically-derived key" in str(report.violations[0])

    def test_split_keys_clean(self):
        def good(key, x):
            k1, k2 = jax.random.split(key)
            return (jax.random.normal(k1, x.shape)
                    + jax.random.normal(k2, x.shape) + x)

        report = V.key_reuse(V.trace(good, KEY, jnp.ones((4,))))
        assert report.ok
        assert report.summary["distinct_keys"] == 2

    def test_flags_reuse_inside_scan_body(self):
        """Two sites consuming the same carried key inside one scan."""
        def bad(key, xs):
            def body(c, x):
                a = jax.random.normal(key, ())
                b = jax.random.normal(key, ())
                return c + a + b + x, None

            out, _ = jax.lax.scan(body, 0.0, xs)
            return out

        report = V.key_reuse(V.trace(bad, KEY, jnp.arange(5.0)))
        assert not report.ok

    def test_per_iteration_fold_clean(self):
        """The engine's block-key discipline: fold per index, one site."""
        def good(key, xs):
            def body(c, i):
                k = jax.random.fold_in(key, i)
                return c + jax.random.normal(k, ()), None

            out, _ = jax.lax.scan(body, 0.0, xs)
            return out

        assert V.key_reuse(V.trace(good, KEY, jnp.arange(5))).ok

    def test_flags_baked_key(self):
        def baked(x):
            return jax.random.normal(jax.random.PRNGKey(0), x.shape) + x

        report = V.key_reuse(V.trace(baked, jnp.ones((4,))))
        assert not report.ok
        assert "not derived from any traced key argument" in \
            str(report.violations[0])
        # procedural matrix content waives the baked check, not the reuse one
        assert V.key_reuse(V.trace(baked, jnp.ones((4,))),
                           allow_baked=True).ok

    def test_attribution_names_consumption_site(self):
        def bad(key, x):
            return (jax.random.normal(key, x.shape)
                    + jax.random.normal(key, x.shape) + x)

        msg = str(V.key_reuse(V.trace(bad, KEY, jnp.ones((4,)))).violations[0])
        assert re.search(
            r"KeyReuse: 2 consumptions of identically-derived key "
            r"\(sites: .*random_bits @ test_verify\.py:\d+ \(in bad\)", msg), msg


# ----------------------------------------------------------- PrecisionLint
class TestPrecisionLint:
    def test_flags_f16_accumulator(self):
        """The known-bad precision pipeline: a float16 scan carry."""
        def f16_acc(xs):
            def body(c, x):
                return c + x.astype(jnp.float16), None

            out, _ = jax.lax.scan(body, jnp.float16(0), xs)
            return out

        report = V.precision_lint(V.trace(f16_acc, jnp.ones((5,))))
        assert not report.ok
        assert report.summary["sub_f32_carries"] == 1
        assert re.search(
            r"PrecisionLint: float16 loop carry float16\[\] "
            r"\(sub-f32 accumulator\) \[scan @ test_verify\.py:\d+",
            str(report.violations[0]))

    def test_f32_carry_clean(self):
        def acc(xs):
            out, _ = jax.lax.scan(lambda c, x: (c + x, None), 0.0, xs)
            return out

        assert V.precision_lint(V.trace(acc, jnp.ones((5,)))).ok

    def test_flags_f64_leak(self):
        with jax.enable_x64(True):
            jx = V.trace(lambda x: x.astype(jnp.float64).sum() * 2.0,
                         jnp.ones((4,), jnp.float32))
        report = V.precision_lint(jx)
        assert not report.ok
        assert report.summary["f64_avals"] > 0
        assert "silent f64 leak" in str(report.violations[0])
        assert V.precision_lint(jx, allow_f64=True).ok


# ----------------------------------------------------------- CollectiveAudit
def _shard_mapped(body):
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    return jax.shard_map(body, mesh=mesh, in_specs=P("data", "model"),
                         out_specs=P("data", "model"), check_vma=False)


class TestCollectiveAudit:
    def test_flags_stray_all_gather(self):
        """The known-bad collective pipeline: gathers a full sharded
        operand inside shard_map."""
        def body(blk):
            g = jax.lax.all_gather(blk, "data")       # ships > a block
            return blk + g[0]

        jx = V.trace(_shard_mapped(body), jnp.ones((8, 8)))
        report = V.collective_audit(jx, allowed_axes=("data", "model"),
                                    per_device_budget=16)
        assert not report.ok
        assert report.summary["gathers"] == 1
        assert re.search(
            r"CollectiveAudit: all_gather moves 64 elements > per-device "
            r"budget 16 \[shard_map/all_gather @ test_verify\.py:\d+",
            str(report.violations[0]))

    def test_flags_undeclared_psum_axis(self):
        def body(blk):
            return jax.lax.psum(blk, "data")          # row axis not declared

        jx = V.trace(_shard_mapped(body), jnp.ones((8, 8)))
        report = V.collective_audit(jx, allowed_axes=("model",),
                                    per_device_budget=10_000)
        assert not report.ok
        assert "psum over undeclared axes ['data']" in \
            str(report.violations[0])

    def test_declared_psum_clean(self):
        def body(blk):
            return jax.lax.psum(blk, "model")

        jx = V.trace(_shard_mapped(body), jnp.ones((8, 8)))
        report = V.collective_audit(jx, allowed_axes=("data", "model"),
                                    per_device_budget=10_000)
        assert report.ok
        assert report.summary["psums"] == 1


# ----------------------------------------------------------- registry + gate
class TestPipelineRegistry:
    def test_registry_covers_required_matrix(self):
        from repro.analysis import pipelines as P
        specs = P.registered_pipelines()
        assert len(specs) >= 12
        names = {s.name for s in specs}
        # distributed resident=False forward AND rmatvec at virtual 65,536^2
        assert "distributed-virtual65536-forward-1x1" in names
        assert "distributed-virtual65536-rmatvec-1x1" in names
        assert {s.placement for s in specs} == \
            {"local", "streamed", "distributed"}
        assert {s.backend for s in specs} == {"reference", "pallas"}
        assert {"forward", "rmatvec", "solve"} <= {s.direction for s in specs}
        assert any(s.direction == "solve" and "cg" in s.name for s in specs)
        assert any(s.direction == "solve" and "pdhg" in s.name for s in specs)

    def test_virtual_65536_pipeline_proves_block_bound(self):
        """The paper-scale structural claim, end to end through the
        registry: the virtual 65,536^2 forward MVM traces with a
        high-water mark of ONE capacity block and no violations."""
        from repro.analysis import pipelines as P
        spec = {s.name: s for s in P.registered_pipelines()}[
            "distributed-virtual65536-forward-1x1"]
        reports = P.verify_pipeline(spec)
        for name, report in reports.items():
            assert report.ok, (name, [str(v) for v in report.violations])
        assert reports["AvalBound"].summary["max_elements"] == \
            P.VIRTUAL_CAP * P.VIRTUAL_CAP
        assert reports["DispatchCount"].summary["dispatch_boundaries"] == 1

    def test_manifest_matches_registry(self):
        """INVARIANTS.json rows exist for every 1-device pipeline and
        record no violations (full cross-check is the CI gate)."""
        import json
        import pathlib
        manifest = json.loads(
            (pathlib.Path(__file__).resolve().parent.parent
             / "INVARIANTS.json").read_text())
        from repro.analysis import pipelines as P
        for spec in P.registered_pipelines():
            assert spec.name in manifest, spec.name
            assert manifest[spec.name]["violations"] == []
            assert manifest[spec.name]["max_elements"] <= \
                manifest[spec.name]["aval_budget"]
