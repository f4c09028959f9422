"""repro.serving: traffic determinism, cache policies, batching invariants,
fused decode dispatch, and end-to-end simulator replay."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import RRAMBackendConfig
from repro.configs.registry import get_arch, model_module
from repro.core.write_verify import WriteStats
from repro.models import params as P
from repro.models.common import Runtime
from repro.models.rram import (analog_image_bytes, forward_input_stats,
                               is_programmed, program_rram, reprogram_rram,
                               strip_rram)
from repro.serving import (BatchingConfig, CacheOverBudgetError, ImageCache,
                           RequestQueue, ServingConfig, TenantSpec,
                           TrafficConfig, bucket_for, generate_trace,
                           simulate)
from repro.train.serve import Server

RRAM = RRAMBackendConfig(enabled=True)


def _build(arch_name="rwkv6-1.6b", seed=0):
    cfg = get_arch(arch_name).reduced()
    mod = model_module(cfg)
    prm = P.materialize(mod.init_specs(cfg), jax.random.PRNGKey(seed),
                        jnp.float32)
    return cfg, mod, prm


# ---------------------------------------------------------------- traffic

TENANTS = (TenantSpec("a", "rwkv6-1.6b"), TenantSpec("b", "qwen3-1.7b"),
           TenantSpec("c", "rwkv6-1.6b"))


def test_trace_deterministic_and_zipf_ordered():
    cfg = TrafficConfig(n_requests=200, zipf_s=1.3, seed=11)
    t1 = generate_trace(TENANTS, cfg)
    t2 = generate_trace(TENANTS, cfg)
    assert t1 == t2
    assert generate_trace(TENANTS, dataclasses.replace(cfg, seed=12)) != t1
    # arrivals sorted, lengths from the configured mixes
    arr = [r.arrival_s for r in t1]
    assert arr == sorted(arr)
    assert {r.prompt_len for r in t1} <= set(cfg.prompt_lens)
    # Zipf skew: first-listed tenant gets the most traffic
    counts = {t.name: sum(r.tenant == t.name for r in t1) for t in TENANTS}
    assert counts["a"] > counts["b"] > 0


# ------------------------------------------------------------------ cache

def _fake_builder(size, energy, latency=0.01):
    def build():
        return object(), size, WriteStats(
            energy_j=jnp.float32(energy), latency_s=jnp.float32(latency),
            iterations=jnp.int32(1), final_delta=jnp.float32(0.0))
    return build


def _drive(policy, accesses, sizes, energies, capacity):
    cache = ImageCache(capacity, policy)
    t = 0.0
    for key in accesses:
        cache.get(key, _fake_builder(sizes[key], energies[key]), t)
        t += 1.0
    return cache


def test_write_cost_eviction_beats_lru_on_skewed_trace():
    """Hot expensive image + rotating cold cheap tenants: LRU flushes the
    expensive image during cold bursts; write-cost-aware keeps it."""
    sizes = {"big": 600, "s1": 250, "s2": 250, "s3": 250}
    energies = {"big": 4.0, "s1": 0.1, "s2": 0.1, "s3": 0.1}
    rng = np.random.Generator(np.random.PCG64(5))
    accesses = []
    for _ in range(60):  # Zipf-ish: big is ~half of traffic
        accesses.append("big" if rng.random() < 0.5
                        else rng.choice(["s1", "s2", "s3"]))
    lru = _drive("lru", accesses, sizes, energies, capacity=900)
    wc = _drive("write_cost", accesses, sizes, energies, capacity=900)
    assert wc.write_energy_j < lru.write_energy_j
    # under write-cost the expensive image is never reprogrammed after a warm-up hit
    assert wc.entries["big"].hits > 1


def test_never_evict_ooms_the_budget():
    cache = ImageCache(800, "never")
    cache.get("a", _fake_builder(500, 1.0), 0.0)
    with pytest.raises(CacheOverBudgetError):
        cache.get("b", _fake_builder(500, 1.0), 1.0)
    # an entry larger than total capacity always raises
    with pytest.raises(CacheOverBudgetError):
        ImageCache(100, "lru").get("x", _fake_builder(500, 1.0), 0.0)


def test_cache_counters_and_reprograms():
    cache = ImageCache(600, "lru")
    cache.get("a", _fake_builder(400, 1.0), 0.0)
    cache.get("a", _fake_builder(400, 1.0), 1.0)          # hit
    cache.get("b", _fake_builder(400, 2.0), 2.0)          # evicts a
    _, out = cache.get("a", _fake_builder(400, 1.0), 3.0)  # reprogram
    assert (cache.hits, cache.misses, cache.reprograms) == (1, 3, 1)
    assert out.reprogrammed and not out.hit
    assert cache.write_energy_j == pytest.approx(4.0)
    assert cache.evictions == 2


# --------------------------------------------------------------- batching

def test_bucket_for():
    assert bucket_for(5, (4, 8, 16)) == 8
    assert bucket_for(4, (4, 8, 16)) == 4
    with pytest.raises(ValueError):
        bucket_for(20, (4, 8, 16))


def test_batcher_packing_invariants_and_no_starvation():
    cfg = TrafficConfig(n_requests=80, rate_rps=50.0, zipf_s=1.2,
                        prompt_lens=(4, 10), prompt_mix=(0.5, 0.5),
                        decode_lens=(3, 7), decode_mix=(0.5, 0.5), seed=3)
    trace = generate_trace(TENANTS, cfg)
    bcfg = BatchingConfig(max_batch=4, prompt_buckets=(4, 16),
                          decode_buckets=(4, 8), batch_buckets=(1, 2, 4))
    q = RequestQueue(bcfg)
    for r in trace:
        q.add(r)
    service_s = 1.0
    now, starts, n_batches = 0.0, {}, 0
    while len(q):
        b = q.form_batch(now)
        if b is None:
            now = q.next_arrival(now)
            continue
        n_batches += 1
        # packing invariants: one image per batch, shapes padded to buckets
        assert len({(r.tenant, r.arch) for r in b.requests}) == 1
        assert b.size <= bcfg.max_batch
        assert b.batch_pad in bcfg.batch_buckets and b.batch_pad >= b.size
        assert all(r.prompt_len <= b.prompt_bucket for r in b.requests)
        assert all(r.decode_len <= b.decode_bucket for r in b.requests)
        # FIFO head-of-line: the batch contains the oldest waiting request
        oldest = min((r for r in trace if r.rid in
                      {x.rid for x in b.requests} | {x.rid for x in q.waiting}
                      and r.arrival_s <= now),
                     key=lambda r: (r.arrival_s, r.rid))
        assert oldest.rid in {r.rid for r in b.requests}
        for r in b.requests:
            starts[r.rid] = now
        now += service_s
    assert len(starts) == len(trace)
    # no-starvation deadline: FIFO service means a request waits at most one
    # batch-service per request ahead of it in arrival order (plus idle gaps).
    for i, r in enumerate(trace):
        assert starts[r.rid] - r.arrival_s <= (i + 1) * service_s + 1e-9
    assert n_batches < len(trace)   # packing actually happened


# ------------------------------------------------- server / fused decode

def test_server_decode_is_single_fused_dispatch():
    from repro.analysis import verify
    cfg, mod, prm = _build()
    srv = Server(mod, cfg, prm, rt=Runtime(rram=RRAM), max_len=32,
                 key=jax.random.PRNGKey(5))
    caches = jax.eval_shape(lambda: mod.init_caches(2, cfg))
    jaxpr = verify.trace(srv.decode_fn(6),
                         jax.ShapeDtypeStruct((2, 1), jnp.int32), caches)
    rep = verify.dispatch_count(jaxpr, max_top_level=1)
    rep.assert_ok()
    assert rep.summary["dispatch_boundaries"] == 1


def test_fused_generate_matches_stepwise_decode_digital():
    """The scan-fused decode must reproduce the unfused per-token loop
    exactly on the deterministic digital path."""
    cfg, mod, prm = _build()
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab)
    srv = Server(mod, cfg, prm, max_len=32)
    fused = srv.generate({"tokens": toks}, 6)
    # hand-rolled reference loop
    rt = Runtime(key=jax.random.PRNGKey(9))
    logits, caches = mod.prefill(prm, {"tokens": toks}, cfg, rt, 32)
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    out = [tok]
    for _ in range(5):
        logits, caches = mod.decode_step(prm, tok, caches, cfg, rt)
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        out.append(tok)
    np.testing.assert_array_equal(np.asarray(fused),
                                  np.asarray(jnp.concatenate(out, axis=1)))


def test_injectable_key_gives_independent_tenant_draws():
    cfg, mod, prm = _build()
    p1, s1 = program_rram(prm, RRAM, jax.random.PRNGKey(0))
    p2, _ = reprogram_rram(p1, RRAM, jax.random.PRNGKey(1))
    assert is_programmed(p1) and is_programmed(p2)

    def first_wt(tree):
        if isinstance(tree, dict):
            for k, v in tree.items():
                if k == "w_tilde":
                    return np.asarray(v)
                got = first_wt(v)
                if got is not None:
                    return got
        return None

    a, b = first_wt(p1), first_wt(p2)
    assert np.abs(a - b).max() > 0          # independent device draws
    # same key -> identical image (reprogram is deterministic)
    p3, _ = reprogram_rram(p1, RRAM, jax.random.PRNGKey(0))
    np.testing.assert_array_equal(first_wt(p3), a)
    # stripping removes the analog operands
    assert not is_programmed(strip_rram(p1))
    assert analog_image_bytes(p1) > 0 and analog_image_bytes(strip_rram(p1)) == 0
    # pre-programmed params skip programming inside Server
    srv = Server(mod, cfg, p1, rt=Runtime(rram=RRAM), max_len=32)
    assert srv.write_stats is None and srv.params is p1


def test_forward_input_stats_scales_with_batch():
    cfg, mod, prm = _build()
    p, _ = program_rram(prm, RRAM, jax.random.PRNGKey(0))
    s1 = forward_input_stats(p, RRAM, batch=1)
    s4 = forward_input_stats(p, RRAM, batch=4)
    assert float(s1.energy_j) > 0
    assert float(s4.energy_j) == pytest.approx(4 * float(s1.energy_j),
                                               rel=1e-5)


def test_engine_image_nbytes_and_release():
    from repro.engine import AnalogEngine
    from repro.models.rram import crossbar_cfg
    eng = AnalogEngine(crossbar_cfg(RRAM))
    A = eng.program(jax.random.normal(jax.random.PRNGKey(0), (64, 48)),
                    jax.random.PRNGKey(1))
    assert A.image_nbytes > 0
    before = A.image_nbytes
    y = A @ jnp.ones((48,))
    assert y.shape == (64,)
    assert A.image_nbytes == before        # executes add no derived copies
    A.release()
    assert A._scan_exec is None
    assert A.image_nbytes == before


# ------------------------------------------------------------- simulator

def _sim_cfg(rram, n=6, policy="write_cost", run_model=True):
    tenants = (TenantSpec("acme", "rwkv6-1.6b"),
               TenantSpec("initech", "rwkv6-1.6b"))
    traffic = TrafficConfig(n_requests=n, rate_rps=6.0, zipf_s=1.0,
                            prompt_lens=(4, 8), prompt_mix=(0.6, 0.4),
                            decode_lens=(3, 5), decode_mix=(0.6, 0.4), seed=2)
    return ServingConfig(
        tenants=tenants, traffic=traffic,
        batching=BatchingConfig(max_batch=2, prompt_buckets=(4, 8),
                                decode_buckets=(4, 8), batch_buckets=(1, 2)),
        rram=rram, cache_capacity_bytes=1 << 22, policy=policy, seed=0,
        max_len=32, run_model=run_model)


def test_simulator_replay_deterministic_twice_in_one_process():
    cfg = _sim_cfg(RRAM)
    r1 = simulate(cfg)
    r2 = simulate(cfg)
    assert r1.records == r2.records
    assert r1.summary == r2.summary
    assert r1.summary["n_requests"] == 6
    assert r1.summary["joules_per_token"] > 0
    assert r1.cache_stats["misses"] >= 1      # at least one image programmed
    # requests finish after they arrive, with positive service time
    for rec in r1.records:
        assert rec.finish_s > rec.start_s >= rec.arrival_s


def test_simulator_digital_baseline_same_trace():
    ra = simulate(_sim_cfg(RRAM, run_model=False))
    rd = simulate(_sim_cfg(None, run_model=False))
    assert rd.cache_stats is None
    assert rd.summary["write_energy_j"] == 0.0
    # same trace on both backends: identical request ids and token counts
    assert [r.rid for r in ra.records] == [r.rid for r in rd.records]
    assert ra.summary["useful_tokens"] == rd.summary["useful_tokens"]
    # but different clocks/energy (the backends differ)
    assert ra.summary["joules_per_token"] != rd.summary["joules_per_token"]
