"""Unit tests for repro.reliability: aging, probes, refresh, fault-tolerant
solves, and the serving refresh scheduler."""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import solvers
from repro.core import CrossbarConfig, MCAGeometry, get_device
from repro.engine import AnalogEngine
from repro.reliability import (RefreshPolicy, attach_age, fault_probability,
                               ft_cg, ft_pdhg, predicted_residual,
                               probe_tile_scores, probe_vectors, refresh_tiles,
                               select_tiles)
from repro.reliability.aging import AgeLedger, aged_blocks

KEY = jax.random.PRNGKey(0)


def _spd(n: int, key=KEY):
    r = jax.random.normal(key, (n, n), jnp.float32) / n
    a = r + r.T + 2.0 * jnp.eye(n, dtype=jnp.float32)
    x_true = jax.random.normal(jax.random.fold_in(key, 1), (n,), jnp.float32)
    return a, x_true, a @ x_true


def _handle(a, device="epiram", cell=32):
    cfg = CrossbarConfig(device=get_device(device),
                         geom=MCAGeometry(2, 2, cell, cell), k_iters=5,
                         ec=True)
    engine = AnalogEngine(cfg)
    return engine.program(a, jax.random.fold_in(KEY, 7))


# ----------------------------------------------------------------- aging
def test_fault_probability_no_float32_underflow():
    """Regression: 1 - (1 - 1e-9)^N computed naively underflows to 0 in
    float32 (1 - 1e-9 rounds to 1.0) -- the stable form must not."""
    dev = get_device("epiram")           # fault_rate 1e-9
    p = float(fault_probability(dev, 1e5))
    assert p > 0.0
    assert p == pytest.approx(1e-4, rel=0.01)
    # monotone in the MVM count
    assert float(fault_probability(dev, 2e5)) > p


def test_age_ledger_updates_are_functional():
    led = AgeLedger.fresh(KEY, 2, 2)
    led2 = led.advanced(10).elapsed(5.0)
    assert float(led.mvms.max()) == 0.0          # original untouched
    assert float(led2.mvms.min()) == 10.0
    assert float(led2.seconds.min()) == 5.0
    mask = jnp.asarray([[True, False], [False, False]])
    led3 = led2.reset(mask)
    assert float(led3.mvms[0, 0]) == 0.0
    assert float(led3.mvms[1, 1]) == 10.0
    assert int(led3.refresh_count[0, 0]) == 1
    assert int(led3.refresh_count[1, 1]) == 0


def test_aged_blocks_replayable_and_monotone():
    """Same age -> identical fault set; the faulted set only grows with the
    MVM count; a refresh redraws from a fresh fold of the fault keys."""
    a, _, _ = _spd(128)
    A = _handle(a, device="ag-si")        # fault_rate 2e-7: faults show fast
    led = attach_age(A)
    dev = A.engine.cfg.device
    # Age at which the expected number of stuck cells, size * (1 - (1 -
    # rate)^N), is 20: P(no stuck cell) ~ e^-20, so the draw cannot decide.
    n1 = math.ceil(math.log1p(-20 / a.size) / math.log1p(-dev.fault_rate))
    aged1 = aged_blocks(A.at_blocks, led.advanced(n1), dev)
    aged1b = aged_blocks(A.at_blocks, led.advanced(n1), dev)
    np.testing.assert_array_equal(np.asarray(aged1), np.asarray(aged1b))
    stuck1 = np.asarray(jnp.abs(aged1 - A.at_blocks) > 1e-9)
    aged2 = aged_blocks(A.at_blocks, led.advanced(20 * n1), dev)
    stuck2 = np.asarray(jnp.abs(aged2 - A.at_blocks) > 1e-9)
    assert stuck1.sum() > 0
    assert np.all(stuck2[stuck1])                 # faults never heal with age
    assert stuck2.sum() > stuck1.sum()
    refreshed = led.advanced(n1).reset(jnp.ones((2, 2), bool)).advanced(n1)
    aged3 = aged_blocks(A.at_blocks, refreshed, dev)
    stuck3 = np.asarray(jnp.abs(aged3 - A.at_blocks) > 1e-9)
    assert not np.array_equal(stuck3, stuck1)     # refresh redraws the fate


def test_age_zero_is_identity():
    a, _, _ = _spd(128)
    A = _handle(a)
    led = attach_age(A)
    aged = aged_blocks(A.at_blocks, led, A.engine.cfg.device)
    np.testing.assert_array_equal(np.asarray(aged), np.asarray(A.at_blocks))


def test_attach_age_rejects_streamed():
    a, _, _ = _spd(128)
    cfg = CrossbarConfig(device=get_device("epiram"),
                         geom=MCAGeometry(2, 2, 32, 32), k_iters=5, ec=True)
    eng = AnalogEngine(cfg, execution="streamed")
    a_pad = np.asarray(a)
    blocks = a_pad.reshape(2, 64, 2, 64).transpose(0, 2, 1, 3)
    A = eng.program(lambda i, j: jnp.asarray(blocks[i, j]), KEY, shape=a.shape)
    with pytest.raises(ValueError):
        attach_age(A)


def test_predicted_residual_monotone_and_exact_at_zero():
    from repro.core.devices import effective_sigma_py
    dev = get_device("taox-hfox")
    p0 = predicted_residual(dev, k_iters=5, seconds=0.0, mvms=0.0, n=256)
    assert p0 == pytest.approx(effective_sigma_py(dev, 5))
    p_t = predicted_residual(dev, k_iters=5, seconds=100.0, mvms=0.0, n=256)
    p_n = predicted_residual(dev, k_iters=5, seconds=0.0, mvms=1e4, n=256)
    assert p_t > p0 and p_n > p0
    assert predicted_residual(dev, k_iters=5, seconds=200.0, mvms=2e4,
                              n=256) > max(p_t, p_n)


# -------------------------------------------------------- probes + refresh
def test_probe_vectors_unit_norm_block_support():
    x = probe_vectors(100, 4, 32)        # last block is the 4-wide remainder
    assert x.shape == (100, 4)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(x), axis=0),
                               np.ones(4), rtol=1e-5)
    xs = np.asarray(x)
    assert np.all(xs[32:, 0] == 0.0)     # column j supported on block j only
    assert np.all(xs[:32, 1] == 0.0) and np.all(xs[64:, 1] == 0.0)
    assert np.all(xs[:96, 3] == 0.0)


def test_probe_localizes_damaged_tile():
    """Probe scores localize REAL aging damage: the tile holding the worst
    stuck-cell deviation is the probe map's worst entry, and fault-free
    tiles stay near the programming floor.  (Manual ``at_blocks`` edits are
    no good here: tier-1 keeps ``dense() = at + da``, so hand-editing ``at``
    shifts the digital reference by the same delta and cancels.)"""
    a, _, _ = _spd(128)
    A = _handle(a, device="ag-si")
    attach_age(A)
    dev = A.engine.cfg.device
    mvms = int(4 / (dev.fault_rate * a.size))         # ~4 expected faults
    A.age = A.age.advanced(mvms)
    damage = np.asarray(jnp.abs(
        aged_blocks(A.at_blocks, A.age, dev) - A.at_blocks))
    per_tile = damage.max(axis=(2, 3))
    assert per_tile.max() > 0.0                       # the draw did latch cells
    rep = probe_tile_scores(A, key=jax.random.fold_in(KEY, 3))
    s = np.asarray(rep.scores)
    assert s.shape == (2, 2)
    assert np.argmax(s) == np.argmax(per_tile)
    healthy = per_tile == 0.0
    if healthy.any():
        assert s[healthy].max() < 0.05                # near the fresh floor
    assert rep.n_probes == 2
    assert float(rep.input_stats.energy_j) > 0
    # the probe batch aged the image further: nb physical reads
    assert float(A.age.mvms.min()) >= mvms + 2.0


def test_select_tiles_threshold_and_cap():
    scores = np.array([[0.5, 0.01], [0.2, 0.9]])
    assert select_tiles(scores, RefreshPolicy(threshold=0.1)) == \
        ((1, 1), (0, 0), (1, 0))
    assert select_tiles(scores, RefreshPolicy(threshold=0.1, max_tiles=1)) == \
        ((1, 1),)
    assert select_tiles(scores, RefreshPolicy(threshold=2.0)) == ()


def test_refresh_restores_damaged_tile_cheaper_than_full():
    a, _, b = _spd(128)
    bn = float(jnp.linalg.norm(b))
    A = _handle(a, device="ag-si")
    attach_age(A)
    dev = A.engine.cfg.device
    A.age = A.age.advanced(int(4 / (dev.fault_rate * a.size)))
    rep = probe_tile_scores(A, key=jax.random.fold_in(KEY, 3))
    fresh_floor = 0.05                 # above the healthy-tile probe scores
    rr = refresh_tiles(A, rep.scores, RefreshPolicy(threshold=fresh_floor),
                       key=jax.random.fold_in(KEY, 4))
    assert 0 < len(rr.tiles) < 4                   # selective, not a rewrite
    for (i, j) in rr.tiles:
        assert int(A.age.refresh_count[i, j]) == 1
        assert float(A.age.mvms[i, j]) == 0.0
    assert int(np.asarray(A.age.refresh_count).sum()) == len(rr.tiles)
    assert 0 < float(rr.write_stats.energy_j) \
        < float(rr.full_rewrite_stats.energy_j)
    rep2 = probe_tile_scores(A, key=jax.random.fold_in(KEY, 5))
    assert rep2.worst < fresh_floor                # damage gone
    res = solvers.cg(A, b, tol=1e-6, maxiter=60,
                     key=jax.random.fold_in(KEY, 6))
    assert float(jnp.linalg.norm(b - a @ res.x)) / bn < 0.02


def test_refresh_requires_resident_blocks():
    a, _, _ = _spd(128)
    cfg = CrossbarConfig(device=get_device("epiram"),
                         geom=MCAGeometry(2, 2, 32, 32), k_iters=5, ec=True)
    eng = AnalogEngine(cfg, execution="streamed")
    a_pad = np.asarray(a)
    blocks = a_pad.reshape(2, 64, 2, 64).transpose(0, 2, 1, 3)
    A = eng.program(lambda i, j: jnp.asarray(blocks[i, j]), KEY, shape=a.shape)
    with pytest.raises(ValueError):
        refresh_tiles(A, np.ones((2, 2)), RefreshPolicy(threshold=0.0))


# -------------------------------------------------------------- ft solves
def test_ft_cg_healthy_converges_without_restores(tmp_path):
    from repro.distributed.fault_tolerance import CheckpointManager
    a, x_true, b = _spd(128)
    A = _handle(a)
    mgr = CheckpointManager(str(tmp_path))
    res = ft_cg(A, b, tol=1e-4, maxiter=400, segment=25,
                key=jax.random.fold_in(KEY, 9), manager=mgr)
    assert res.converged and res.restores == 0
    assert res.fault_events == ()
    assert res.final_residual < 1e-4
    assert float(jnp.linalg.norm(res.x - x_true)) \
        / float(jnp.linalg.norm(x_true)) < 1e-3
    # each accepted segment checkpointed (plus the step-0 entry state)
    assert mgr.latest_step() == res.iterations
    assert res.ledger.mvms > 0


def test_ft_cg_detects_and_recovers_injected_fault(tmp_path):
    from repro.distributed.fault_tolerance import CheckpointManager
    a, _, b = _spd(128)
    A = _handle(a)
    state = {"saved": None}

    def inject(seg, h):
        if seg == 1 and state["saved"] is None:
            state["saved"] = h.at_blocks
            blocks = np.array(jax.device_get(h.at_blocks))
            blocks[:, 0, :, 3] = np.max(np.abs(blocks))
            h.at_blocks = jnp.asarray(blocks)
            h.release()

    def repair(event, h):
        h.at_blocks = state["saved"]
        h.release()

    res = ft_cg(A, b, tol=1e-4, maxiter=400, segment=25,
                key=jax.random.fold_in(KEY, 9),
                manager=CheckpointManager(str(tmp_path)),
                segment_hook=inject, on_fault=repair)
    assert res.converged, res
    assert res.restores == 1
    assert len(res.fault_events) == 1
    assert res.fault_events[0].kind in ("nan", "residual-spike")
    assert res.final_residual < 1e-4


def test_ft_cg_unrepaired_fault_gives_honest_failure(tmp_path):
    """No on_fault repair: the wrapper keeps restoring until max_restores,
    then reports converged=False -- never a silent wrong answer."""
    from repro.distributed.fault_tolerance import CheckpointManager
    a, _, b = _spd(128)
    A = _handle(a)
    done = {"injected": False}

    def inject(seg, h):
        if not done["injected"]:
            done["injected"] = True
            blocks = np.array(jax.device_get(h.at_blocks))
            blocks[:, 0, :, 3] = np.max(np.abs(blocks))
            h.at_blocks = jnp.asarray(blocks)
            h.release()

    res = ft_cg(A, b, tol=1e-6, maxiter=400, segment=25,
                key=jax.random.fold_in(KEY, 9),
                manager=CheckpointManager(str(tmp_path)),
                segment_hook=inject, max_restores=2)
    assert not res.converged
    assert res.restores == 3              # max_restores + the breaking one


def test_ft_pdhg_healthy_lp(tmp_path):
    from repro.distributed.fault_tolerance import CheckpointManager
    a, b, c, x_star, _ = solvers.random_feasible_lp(
        jax.random.fold_in(KEY, 11), 48, 64)
    A = _handle(np.asarray(a), cell=16)
    # tol must sit above the analog KKT floor for this device/size (~2e-2)
    res = ft_pdhg(A, b, c, tol=5e-2, maxiter=3000, segment=200,
                  key=jax.random.fold_in(KEY, 12),
                  manager=CheckpointManager(str(tmp_path)))
    assert res.converged, res
    assert res.restores == 0
    obj_star = float(c @ x_star)
    assert abs(float(c @ res.x) - obj_star) / (1 + abs(obj_star)) < 0.1
    assert res.dual is not None


def test_ft_pdhg_recovers_from_nan_fault(tmp_path):
    from repro.distributed.fault_tolerance import CheckpointManager
    a, b, c, _, _ = solvers.random_feasible_lp(
        jax.random.fold_in(KEY, 11), 48, 64)
    A = _handle(np.asarray(a), cell=16)
    state = {"saved": None}

    def inject(seg, h):
        # seg 0: this LP can converge within one segment, so the fault must
        # land before the first inner solve to be seen at all
        if state["saved"] is None:
            state["saved"] = h.at_blocks
            blocks = np.array(jax.device_get(h.at_blocks))
            blocks[0, 0, 0, 0] = np.nan
            h.at_blocks = jnp.asarray(blocks)
            h.release()

    def repair(event, h):
        h.at_blocks = state["saved"]
        h.release()

    res = ft_pdhg(A, b, c, tol=5e-2, maxiter=3000, segment=200,
                  key=jax.random.fold_in(KEY, 12),
                  manager=CheckpointManager(str(tmp_path)),
                  segment_hook=inject, on_fault=repair)
    assert res.converged, res
    assert res.restores == 1
    assert len(res.fault_events) == 1


def test_divergence_param_none_is_default_numerics():
    """divergence=None must leave the solver numerics (and jaxpr) untouched;
    a huge finite margin must not change a healthy solve either."""
    a, _, b = _spd(64)
    r0 = solvers.cg(a, b, tol=1e-6, maxiter=40)
    r1 = solvers.cg(a, b, tol=1e-6, maxiter=40, divergence=None)
    r2 = solvers.cg(a, b, tol=1e-6, maxiter=40, divergence=1e9)
    np.testing.assert_array_equal(np.asarray(r0.x), np.asarray(r1.x))
    np.testing.assert_allclose(np.asarray(r0.x), np.asarray(r2.x), atol=1e-6)
    assert r0.iterations == r1.iterations == r2.iterations


# ------------------------------------------------------- serving scheduler
def test_serving_refresh_scheduler_bills_and_replays():
    from repro.configs.base import RRAMBackendConfig
    from repro.serving import (ReliabilityConfig, ServingConfig, TenantSpec,
                               TrafficConfig, simulate)
    tenants = (TenantSpec("a", "zamba2-1.2b"), TenantSpec("b", "zamba2-1.2b"))
    traffic = TrafficConfig(n_requests=16, rate_rps=4.0, seed=3)
    rram = RRAMBackendConfig(enabled=True, device="ag-si", k_iters=3)
    base = dict(tenants=tenants, traffic=traffic, rram=rram, run_model=False)

    r0 = simulate(ServingConfig(**base))
    assert "reliability" not in r0.summary          # off by default
    assert "refreshes" in r0.cache_stats

    rel = ReliabilityConfig(refresh_threshold=0.05, refresh_fraction=0.25)
    r1 = simulate(ServingConfig(**base, reliability=rel))
    rs = r1.summary["reliability"]
    assert rs["refreshes"] > 0
    assert rs["refresh_energy_j"] > 0
    assert rs["refresh_stall_s"] > 0
    assert 0 < rs["mean_predicted_residual"] \
        <= rs["max_predicted_residual"] + 1e-12
    # refresh energy lands in the cache's write ledger -> joules/token
    assert r1.cache_stats["refreshes"] == rs["refreshes"]
    assert r1.cache_stats["write_energy_j"] > r0.cache_stats["write_energy_j"]
    # a loose threshold schedules no refreshes but still reports health
    r2 = simulate(ServingConfig(**base, reliability=ReliabilityConfig(
        refresh_threshold=1e9)))
    rs2 = r2.summary["reliability"]
    assert rs2["refreshes"] == 0
    assert rs2["max_predicted_residual"] > rs["max_predicted_residual"]
    # deterministic replay
    r1b = simulate(ServingConfig(**base, reliability=rel))
    assert r1b.summary == r1.summary and r1b.records == r1.records
