"""Distributed tests: run in a subprocess with 8 virtual host devices so the
main pytest process keeps a single device (per the dry-run isolation rule)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_child(code: str, timeout: int = 600) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=timeout)
    assert out.returncode == 0, f"child failed:\n{out.stdout}\n{out.stderr}"
    return json.loads(out.stdout.splitlines()[-1])


PRELUDE = textwrap.dedent("""
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
""")


def test_distributed_mvm_matches_reference():
    res = run_child(PRELUDE + textwrap.dedent("""
        from repro.core import (CrossbarConfig, MCAGeometry,
                                distributed_corrected_mvm, get_device, rel_l2)
        key = jax.random.PRNGKey(0)
        a = jax.random.normal(key, (256, 256))
        x = jax.random.normal(jax.random.fold_in(key, 1), (256,))
        cfg = CrossbarConfig(device=get_device("taox-hfox"),
                             geom=MCAGeometry(2, 2, 32, 32), k_iters=5, ec=True)
        y, st = distributed_corrected_mvm(a, x, key, cfg, mesh)
        raw_cfg = CrossbarConfig(device=get_device("taox-hfox"),
                                 geom=MCAGeometry(2, 2, 32, 32), k_iters=5, ec=False)
        y2, _ = distributed_corrected_mvm(a, x, key, raw_cfg, mesh)
        b = a @ x
        print(json.dumps({"ec": float(rel_l2(y, b)), "raw": float(rel_l2(y2, b)),
                          "E": float(st.energy_j)}))
    """))
    assert res["ec"] < 0.3 * res["raw"]
    assert res["E"] > 0


def test_analog_engine_distributed_program_once():
    """The distributed execution mode behind AnalogEngine: programmed once,
    executed twice, parity with the legacy one-shot entry point."""
    res = run_child(PRELUDE + textwrap.dedent("""
        from repro.core import (CrossbarConfig, MCAGeometry,
                                distributed_corrected_mvm, get_device, rel_l2)
        from repro.engine import AnalogEngine
        key = jax.random.PRNGKey(0)
        a = jax.random.normal(key, (256, 256)) / 16
        x = jax.random.normal(jax.random.fold_in(key, 1), (256,))
        cfg = CrossbarConfig(device=get_device("taox-hfox"),
                             geom=MCAGeometry(2, 2, 32, 32), k_iters=5, ec=True)
        y_legacy, st = distributed_corrected_mvm(a, x, key, cfg, mesh)
        eng = AnalogEngine(cfg, execution="distributed", mesh=mesh)
        A = eng.program(a, key)
        y1, ist = eng.mvm_with_stats(A, x)
        y2 = A @ x                     # second execution, zero re-programming
        b = a @ x
        print(json.dumps({
            "parity": float(rel_l2(y1, y_legacy)),
            "err1": float(rel_l2(y1, b)), "err2": float(rel_l2(y2, b)),
            "E_prog": float(A.write_stats.energy_j),
            "E_call": float(ist.energy_j), "E_legacy": float(st.energy_j)}))
    """))
    assert res["parity"] <= 1e-5
    assert res["err1"] < 0.1 and res["err2"] < 0.1
    assert res["E_prog"] > 0 and res["E_call"] > 0
    # legacy one-shot accounting == program + one input write
    assert abs(res["E_prog"] + res["E_call"] - res["E_legacy"]) \
        <= 1e-6 * res["E_legacy"]


def test_distributed_producer_matches_streamed():
    """Producer-driven distributed programming/MVM on a real 2x4 mesh: the
    global block-key schedule makes the mesh-sharded image bit-identical to
    the single-device streamed image; MVM values agree <= 1e-5 across the
    resident, virtual (resident=False) and pallas-backend paths; the output
    stays row-sharded."""
    res = run_child(PRELUDE + textwrap.dedent("""
        from repro.core import CrossbarConfig, MCAGeometry, get_device, rel_l2
        from repro.core.distributed import pallas_shard_map_supported
        from repro.engine import AnalogEngine
        key = jax.random.PRNGKey(0)
        cfg = CrossbarConfig(device=get_device("taox-hfox"),
                             geom=MCAGeometry(1, 1, 32, 32), k_iters=5,
                             ec=True)
        n = 256                                   # 8x8 grid of 32^2 blocks
        a = jax.random.normal(key, (n, n)) / 16
        blocks = a.reshape(8, 32, 8, 32).transpose(0, 2, 1, 3)
        producer = lambda i, j: blocks[i, j]
        x = jax.random.normal(jax.random.fold_in(key, 1), (n,))

        st = AnalogEngine(cfg, execution="streamed")
        A_s = st.program(producer, key, shape=(n, n))
        y_s = st.mvm(A_s, x, key=key)

        de = AnalogEngine(cfg, execution="distributed", mesh=mesh)
        A_d = de.program(producer, key, shape=(n, n))
        image_equal = bool(jnp.array_equal(A_d.at_blocks, A_s.at_blocks))
        y_d = de.mvm(A_d, x, key=key)
        row_sharded = "data" in str(y_d.sharding.spec)

        A_v = de.program(producer, key, shape=(n, n), resident=False)
        y_v = de.mvm(A_v, x, key=key)

        pallas_ok = pallas_shard_map_supported(mesh)
        if pallas_ok:
            dp = AnalogEngine(cfg, execution="distributed", backend="pallas",
                              mesh=mesh)
            A_p = dp.program(producer, key, shape=(n, n))
            pallas_par = float(rel_l2(dp.mvm(A_p, x, key=key), y_d))
            # dense placement through the same kernel tile step
            A_pd = dp.program(a, key)
            A_rd = de.program(a, key)
            pallas_dense = float(rel_l2(dp.mvm(A_pd, x, key=key),
                                        de.mvm(A_rd, x, key=key)))
        else:
            pallas_par = pallas_dense = -1.0  # documented fallback: reference
        b = a @ x
        print(json.dumps({
            "image_equal": image_equal, "row_sharded": row_sharded,
            "mvm": float(rel_l2(y_d, y_s)), "virt": float(rel_l2(y_v, y_d)),
            "pallas_ok": bool(pallas_ok), "pallas": pallas_par,
            "pallas_dense": pallas_dense,
            "err": float(rel_l2(y_d, b))}))
    """))
    assert res["image_equal"]
    assert res["row_sharded"]
    assert res["mvm"] <= 1e-5
    assert res["virt"] <= 1e-5
    # pallas either passes reference parity or reported its probe fallback
    if res["pallas_ok"]:
        assert res["pallas"] <= 1e-5
        assert res["pallas_dense"] <= 1e-5
    assert res["err"] < 0.1


def test_distributed_rmvm_matches_streamed():
    """Transposed corrected MVMs (A.T @ y) on a real 2x4 mesh: the global
    block-key schedule makes the mesh-sharded transposed sweep agree <= 1e-5
    with the single-device streamed transposed sweep across the resident,
    virtual (resident=False), pallas and dense placements; partials psum over
    the ROW axes and the output comes back COLUMN-sharded."""
    res = run_child(PRELUDE + textwrap.dedent("""
        from repro.core import CrossbarConfig, MCAGeometry, get_device, rel_l2
        from repro.core.distributed import pallas_shard_map_supported
        from repro.engine import AnalogEngine
        key = jax.random.PRNGKey(0)
        cfg = CrossbarConfig(device=get_device("taox-hfox"),
                             geom=MCAGeometry(1, 1, 32, 32), k_iters=5,
                             ec=True)
        n = 256                                   # 8x8 grid of 32^2 blocks
        a = jax.random.normal(key, (n, n)) / 16
        blocks = a.reshape(8, 32, 8, 32).transpose(0, 2, 1, 3)
        producer = lambda i, j: blocks[i, j]
        y = jax.random.normal(jax.random.fold_in(key, 1), (n,))

        st = AnalogEngine(cfg, execution="streamed")
        A_s = st.program(producer, key, shape=(n, n))
        z_s = st.rmvm(A_s, y, key=key)

        de = AnalogEngine(cfg, execution="distributed", mesh=mesh)
        A_d = de.program(producer, key, shape=(n, n))
        z_d = de.rmvm(A_d, y, key=key)
        col_sharded = "model" in str(z_d.sharding.spec)

        A_v = de.program(producer, key, shape=(n, n), resident=False)
        z_v = de.rmvm(A_v, y, key=key)

        A_dd = de.program(a, key)
        z_dd = de.rmvm(A_dd, y, key=key)

        pallas_ok = pallas_shard_map_supported(mesh)
        if pallas_ok:
            dp = AnalogEngine(cfg, execution="distributed", backend="pallas",
                              mesh=mesh)
            A_p = dp.program(producer, key, shape=(n, n))
            pallas_par = float(rel_l2(dp.rmvm(A_p, y, key=key), z_d))
        else:
            pallas_par = -1.0
        b = a.T @ y
        print(json.dumps({
            "col_sharded": col_sharded,
            "mvm": float(rel_l2(z_d, z_s)), "virt": float(rel_l2(z_v, z_d)),
            "dense_err": float(rel_l2(z_dd, b)),
            "pallas_ok": bool(pallas_ok), "pallas": pallas_par,
            "err": float(rel_l2(z_d, b))}))
    """))
    assert res["col_sharded"]
    assert res["mvm"] <= 1e-5
    assert res["virt"] <= 1e-5
    if res["pallas_ok"]:
        assert res["pallas"] <= 1e-5
    assert res["err"] < 0.1 and res["dense_err"] < 0.1


def test_distributed_pdhg_lp():
    """Acceptance: a random feasible LP solved by PDHG over a 2x4 mesh with a
    resident=False procedural producer -- corrected analog matvec + rmatvec
    only, objective within 1e-3 of the digital PDHG oracle, and NEITHER the
    forward nor the transposed jitted MVM ever traces an A-sized aval
    (statically asserted via the AvalBound pass)."""
    res = run_child(PRELUDE + textwrap.dedent("""
        from repro import solvers
        from repro.analysis import CallCounter, aval_bound, dispatch_count, \\
            trace
        from repro.core import CrossbarConfig, MCAGeometry, get_device, rel_l2
        from repro.core.matrices import ImplicitBandedMatrix
        from repro.engine import AnalogEngine
        key = jax.random.PRNGKey(0)
        cfg = CrossbarConfig(device=get_device("epiram"),
                             geom=MCAGeometry(1, 1, 32, 32), k_iters=5,
                             ec=True)
        n = 256
        imp = ImplicitBandedMatrix(n=n, cap_m=32, cap_n=32, seed=7)
        producer = CallCounter(imp.block)

        de = AnalogEngine(cfg, execution="distributed", mesh=mesh)
        A = de.program(producer, key, shape=(n, n), resident=False)
        a = A.dense()                  # host-side oracle materialization
        # feasible LP with known structure: complementary (x*, s) split
        u = jax.random.normal(jax.random.fold_in(key, 1), (n,), jnp.float32)
        x_star = jnp.maximum(u, 0.0)
        s = jnp.maximum(-u, 0.0)
        y_star = jax.random.normal(jax.random.fold_in(key, 2), (n,),
                                   jnp.float32) / 4
        b = a @ x_star
        c = a.T @ y_star + s

        specs = (jax.ShapeDtypeStruct((n,), jnp.float32),
                 jax.ShapeDtypeStruct(key.shape, key.dtype))
        jx_fwd = trace(de.mvm_fn(A), *specs)
        fwd = aval_bound(jx_fwd, budget=n * n // 8)
        fwd.assert_ok()
        t = aval_bound(trace(de.mvm_fn(A, transpose=True), *specs),
                       budget=n * n // 8)
        t.assert_ok()
        dispatch_count(jx_fwd, max_top_level=8).assert_ok()
        after_program = producer.calls

        digital = solvers.pdhg(a, b, c, tol=1e-6, maxiter=30000)
        res = solvers.pdhg(A, b, c, tol=3e-4, maxiter=30000, key=key)
        solve_traces = producer.calls - after_program
        obj_a = float(c @ res.x)
        obj_d = float(c @ digital.x)
        print(json.dumps({
            "iters": int(res.iterations), "converged": bool(res.converged),
            "resid": float(res.final_residual),
            "obj_gap": abs(obj_a - obj_d) / (1 + abs(obj_d)),
            "traces": int(solve_traces),
            "max_fwd": int(fwd.summary["max_elements"]),
            "max_t": int(t.summary["max_elements"]), "A_elems": n * n,
            "E": float(res.ledger.total_energy_j),
            "mvms": int(res.ledger.mvms), "mvms_t": int(res.ledger.mvms_t)}))
    """), timeout=900)
    assert res["converged"] and res["resid"] <= 3e-4
    assert res["obj_gap"] <= 1e-3, res
    # forward AND transposed pipelines bound strictly below A
    assert res["max_fwd"] * 8 <= res["A_elems"], res
    assert res["max_t"] * 8 <= res["A_elems"], res
    # one solve core (fwd + transposed traces): never per-block/per-iteration
    assert res["traces"] <= 6, res
    assert res["mvms"] == res["iters"] + 1 and res["mvms_t"] == res["mvms"]
    assert res["E"] > 0


def test_distributed_producer_solve():
    """End-to-end sharded CG through repro.solvers on a 2x4 mesh: one
    compiled program per solve (producer invoked for traces only), converges,
    matches the digital oracle, and the virtual handle's jitted MVM never
    traces an A-sized aval."""
    res = run_child(PRELUDE + textwrap.dedent("""
        from repro import solvers
        from repro.analysis import CallCounter, aval_bound, trace
        from repro.core import CrossbarConfig, MCAGeometry, get_device, rel_l2
        from repro.engine import AnalogEngine
        from repro.core.matrices import ImplicitBandedMatrix
        key = jax.random.PRNGKey(0)
        cfg = CrossbarConfig(device=get_device("epiram"),
                             geom=MCAGeometry(1, 1, 32, 32), k_iters=5,
                             ec=True)
        n = 256
        # procedural producer: nothing A-sized ever closes over the pipeline
        imp = ImplicitBandedMatrix(n=n, cap_m=32, cap_n=32, seed=5)
        producer = CallCounter(imp.block)
        x_true = jax.random.normal(jax.random.fold_in(key, 1), (n,),
                                   jnp.float32)

        de = AnalogEngine(cfg, execution="distributed", mesh=mesh)
        A = de.program(producer, key, shape=(n, n), resident=False)
        a = A.dense()                      # host-side oracle materialization
        b = a @ x_true
        bound = aval_bound(
            trace(de.mvm_fn(A), jax.ShapeDtypeStruct((n,), jnp.float32),
                  jax.ShapeDtypeStruct(key.shape, key.dtype)),
            budget=n * n // 8)
        bound.assert_ok()
        after_program = producer.calls
        res = solvers.cg(A, b, tol=1e-3, maxiter=40)
        solve_traces = producer.calls - after_program
        oracle = jnp.linalg.solve(a, b)
        print(json.dumps({
            "iters": int(res.iterations), "converged": bool(res.converged),
            "resid": float(res.final_residual),
            "traces": int(solve_traces),
            "max_elems": int(bound.summary["max_elements"]),
            "A_elems": n * n,
            "xerr": float(rel_l2(res.x, oracle)),
            "E": float(res.ledger.total_energy_j)}))
    """))
    assert res["converged"] and res["resid"] <= 1e-3
    assert res["iters"] >= 2
    # probe and static walk excluded: the solve itself adds at most ~2
    # traces (the jitted core) -- never per-block or per-iteration work
    assert res["traces"] <= 3, res
    assert res["max_elems"] * 8 <= res["A_elems"], res   # strictly sub-A
    assert res["xerr"] < 5e-3
    assert res["E"] > 0


@pytest.mark.slow
def test_distributed_scale_65536():
    """The acceptance-scale case: n=65,536 >= the paper's largest problem,
    programmed from a procedural producer over a 2x4 mesh with
    resident=False and SOLVED (CG) -- converging with no A-sized array ever
    allocated (statically asserted on the exact jitted MVM)."""
    res = run_child(PRELUDE + textwrap.dedent("""
        from repro import solvers
        from repro.analysis import CallCounter, aval_bound, dispatch_count, \\
            trace
        from repro.core import CrossbarConfig, MCAGeometry, get_device
        from repro.engine import AnalogEngine
        n, cap = 65536, 2048
        cfg = CrossbarConfig(device=get_device("epiram"),
                             geom=MCAGeometry(1, 1, cap, cap), k_iters=5,
                             ec=True)
        eng = AnalogEngine(cfg, execution="distributed", mesh=mesh)
        def banded(i, j):
            # Deterministic SPD banded generator (traceable, O(block) math):
            # the n^2 encode noise already dominates the sweep, so the
            # producer itself stays RNG-free to keep the test CPU-feasible.
            rows = i * cap + jnp.arange(cap)[:, None]
            cols = j * cap + jnp.arange(cap)[None, :]
            dist = jnp.abs(rows - cols)
            blk = jnp.where(dist <= 8,
                            1.0 / (1.0 + dist.astype(jnp.float32)), 0.0)
            return blk + 16.0 * (rows == cols)
        producer = CallCounter(banded)
        key = jax.random.PRNGKey(0)
        A = eng.program(producer, key, shape=(n, n), resident=False)
        jx = trace(eng.mvm_fn(A),
                   jax.ShapeDtypeStruct((n,), jnp.float32),
                   jax.ShapeDtypeStruct(key.shape, key.dtype))
        # paper-scale proof on the exact jitted MVM: high-water mark is
        # O(one capacity block) and the whole sweep is one fused dispatch
        bound = aval_bound(jx, budget=16 * cap * cap)
        bound.assert_ok()
        dispatch_count(jx, max_top_level=8,
                       producer_calls=producer.calls,
                       max_producer_calls=3).assert_ok()
        b = jnp.ones((n,), jnp.float32)
        res = solvers.cg(A, b, tol=2e-2, maxiter=4, key=key)
        print(json.dumps({
            "iters": int(res.iterations), "converged": bool(res.converged),
            "resid": float(res.final_residual), "calls": producer.calls,
            "max_elems": int(bound.summary["max_elements"]),
            "A_elems": n * n,
            "E_write": float(res.ledger.write_energy_j)}))
    """), timeout=1500)
    assert res["converged"], res
    assert res["iters"] >= 1 and res["resid"] <= 2e-2
    # no A-sized allocation: high-water mark is O(one capacity block)
    assert res["max_elems"] * 100 <= res["A_elems"], res
    assert res["calls"] <= 4                      # traces only, never mb*nb
    assert res["E_write"] > 0


def test_compressed_psum_and_ring_matmul():
    res = run_child(PRELUDE + textwrap.dedent("""
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.distributed.collectives import (compressed_psum,
                                                   ring_collective_matmul)
        key = jax.random.PRNGKey(0)
        g = jax.random.normal(key, (8, 64))    # 8 shards over 'data'+'model'

        def red(x):
            out, resid = compressed_psum(x, "data", None)
            return out, resid
        f = jax.jit(jax.shard_map(red, mesh=mesh,
                              in_specs=P(("data",), None),
                              out_specs=(P("data", None), P("data", None))))
        out, resid = f(g)
        # exact sum across the 2 'data' shards:
        exact = g[:4] + g[4:]
        err = float(jnp.max(jnp.abs(out[:4] - exact)) / jnp.max(jnp.abs(exact)))

        # ring collective matmul == dense matmul
        x = jax.random.normal(key, (16, 64))
        w = jax.random.normal(jax.random.fold_in(key, 1), (64, 32))
        def ring(xx, ww):
            return ring_collective_matmul(xx, ww, "model")
        # the ring result is value-replicated over 'model' but the static vma
        # checker cannot prove it -> check_vma=False
        rm = jax.jit(jax.shard_map(ring, mesh=mesh,
                               in_specs=(P(None, None), P("model", None)),
                               out_specs=P(None, None), check_vma=False))
        y = rm(x, w)
        merr = float(jnp.max(jnp.abs(y - x @ w)))
        print(json.dumps({"int8_err": err, "ring_err": merr}))
    """))
    assert res["int8_err"] < 0.02      # int8 quantization error bound
    assert res["ring_err"] < 1e-3


def test_sharded_train_step_matches_single_device():
    res = run_child(PRELUDE + textwrap.dedent("""
        from repro.configs import get_arch, model_module
        from repro.configs.base import TrainConfig
        from repro.models import params as PM
        from repro.train.train_loop import make_train_step
        from repro.train.optimizer import adamw_init
        from repro.launch.steps import build_cell
        from repro.distributed.sharding import param_pspecs, batch_pspec
        from jax.sharding import NamedSharding, PartitionSpec as P

        arch = get_arch("qwen3-1.7b"); cfg = arch.reduced()
        mod = model_module(cfg)
        prm = PM.materialize(mod.init_specs(cfg), jax.random.PRNGKey(0))
        opt = adamw_init(prm)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab)
        batch = {"tokens": tokens, "labels": tokens}
        tcfg = TrainConfig(microbatch=4)
        step = make_train_step(mod, cfg, tcfg)

        # single device result
        p1, o1, m1 = jax.jit(step)(prm, opt, batch)

        # sharded result
        specs = mod.init_specs(cfg)
        psh = jax.tree.map(lambda ps: NamedSharding(mesh, ps),
                           param_pspecs(specs, mesh, "fsdp_tp"))
        prm_s = jax.tree.map(lambda a, s: jax.device_put(a, s), prm, psh)
        with jax.set_mesh(mesh):
            p2, o2, m2 = jax.jit(step)(prm_s, opt, batch)
        print(json.dumps({
            "loss1": float(m1["loss"]), "loss2": float(m2["loss"]),
            "gn1": float(m1["grad_norm"]), "gn2": float(m2["grad_norm"])}))
    """))
    assert abs(res["loss1"] - res["loss2"]) < 1e-3
    assert abs(res["gn1"] - res["gn2"]) / max(res["gn1"], 1e-9) < 5e-3


def test_elastic_checkpoint_restore():
    """Save on a (2,4) mesh, restore onto a (4,2) mesh -- elastic rescale."""
    res = run_child(PRELUDE + textwrap.dedent("""
        import tempfile
        from repro.configs import get_arch, model_module
        from repro.models import params as PM
        from repro.distributed import CheckpointManager
        from repro.distributed.sharding import param_pspecs
        from jax.sharding import NamedSharding

        arch = get_arch("qwen3-1.7b"); cfg = arch.reduced()
        mod = model_module(cfg)
        specs = mod.init_specs(cfg)
        prm = PM.materialize(specs, jax.random.PRNGKey(0))
        sh1 = jax.tree.map(lambda ps: NamedSharding(mesh, ps),
                           param_pspecs(specs, mesh, "tp"))
        prm = jax.tree.map(lambda a, s: jax.device_put(a, s), prm, sh1)
        with tempfile.TemporaryDirectory() as d:
            ck = CheckpointManager(d)
            ck.save(7, {"params": prm}, blocking=True)
            mesh2 = make_mesh((4, 2), ("data", "model"))
            sh2 = jax.tree.map(lambda ps: NamedSharding(mesh2, ps),
                               param_pspecs(specs, mesh2, "fsdp_tp"))
            restored = ck.restore({"params": prm}, shardings={"params": sh2})
            ok = all(np.array_equal(np.asarray(a), np.asarray(b))
                     for a, b in zip(jax.tree.leaves(prm),
                                     jax.tree.leaves(restored["params"])))
            print(json.dumps({"ok": bool(ok), "step": ck.latest_step()}))
    """))
    assert res["ok"] and res["step"] == 7


def test_moe_shard_map_matches_local():
    res = run_child(PRELUDE + textwrap.dedent("""
        from repro.configs import get_arch, model_module
        from repro.models import params as PM
        from repro.models.common import Runtime
        from repro.models import moe as M

        arch = get_arch("mixtral-8x7b"); cfg = arch.reduced()
        lp = PM.materialize(M.moe_specs(cfg), jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model))
        out_local, aux_local = M.moe_apply(lp, x, cfg, Runtime())
        rt = Runtime(mesh=mesh, batch_axes=("data",))
        with jax.set_mesh(mesh):
            out_sm, aux_sm = jax.jit(
                lambda p, xx: M.moe_apply(p, xx, cfg, rt))(lp, x)
        err = float(jnp.max(jnp.abs(out_local - out_sm)))
        print(json.dumps({"err": err, "aux_l": float(aux_local),
                          "aux_s": float(aux_sm)}))
    """))
    assert res["err"] < 2e-2, res
    assert abs(res["aux_l"] - res["aux_s"]) < 2e-2
