"""Device-lifetime reliability end to end: age, probe, refresh, recover.

Act 1 -- the lifetime of ONE programmed image on a faulty device: an SPD
system is programmed once, solved fresh, then aged by the device's own
read-disturb fault process (drift + replayable stuck-at latches, applied
inside the engine's single jitted dispatch).  The aged solve degrades; the
probe panel localizes the damage to specific capacity tiles; a
tile-selective refresh re-runs closed-loop write-and-verify on only those
tiles and restores the solve at a fraction of the full-reprogram energy.

Act 2 -- surviving a fault MID-solve: the same system is programmed across
a 2x4 device mesh and handed to the fault-tolerant CG wrapper.  A stuck
column is injected into the sharded conductance image during segment 1; the
digital residual check (against the healthy reference captured at entry)
flags the divergence, the iterate rolls back to the last good checkpoint on
disk, the ``on_fault`` callback repairs the operator, and the solve
converges anyway.

    PYTHONPATH=src python examples/meliso_reliability.py
    PYTHONPATH=src python examples/meliso_reliability.py --n 512 --mesh 4,2

See DESIGN.md section 12 and docs/reliability.md.
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import CrossbarConfig, MCAGeometry, get_device
from repro.engine import AnalogEngine
from repro.launch.mesh import make_mesh
from repro.reliability import (RefreshPolicy, attach_age, ft_cg,
                               predicted_residual, probe_tile_scores,
                               refresh_tiles)
from repro.solvers import cg
from repro.launch.compile_cache import enable_compile_cache


def _spd(n: int, key: jax.Array):
    r = jax.random.normal(key, (n, n), jnp.float32) / n
    a = r + r.T + 2.0 * jnp.eye(n, dtype=jnp.float32)
    x_true = jax.random.normal(jax.random.fold_in(key, 1), (n,), jnp.float32)
    return a, a @ x_true


def lifetime_act(n: int, device: str) -> None:
    key = jax.random.PRNGKey(0)
    a, b = _spd(n, key)
    bn = float(jnp.linalg.norm(b))
    dev = get_device(device)
    cfg = CrossbarConfig(device=dev, geom=MCAGeometry(2, 2, 32, 32),
                         k_iters=5, ec=True)
    engine = AnalogEngine(cfg)
    A = engine.program(a, jax.random.fold_in(key, 7))   # programmed ONCE
    attach_age(A)

    def digital_rel(salt: int) -> float:
        res = cg(A, b, tol=1e-6, maxiter=120, key=jax.random.fold_in(key, salt))
        return float(jnp.linalg.norm(b - a @ res.x)) / bn

    fresh = digital_rel(11)
    # Age until ~8 cells of the image have latched under read disturb.
    mvms = max(1, int(8.0 / (dev.fault_rate * n * n)))
    A.age = A.age.advanced(mvms)
    pred = predicted_residual(dev, k_iters=cfg.k_iters, seconds=0.0,
                              mvms=mvms, n=n)
    aged = digital_rel(12)
    print(f"[lifetime] n={n} device={device}: fresh solve {fresh:.2e}, "
          f"after {mvms} MVMs aged solve {aged:.2e} "
          f"(analytic prediction {pred:.2e})")
    assert aged > fresh, "aging should visibly degrade the solve"

    report = probe_tile_scores(A, key=jax.random.fold_in(key, 13))
    print("[lifetime] per-tile probe scores (rel l2):")
    for row in np.asarray(report.scores):
        print("            " + "  ".join(f"{s:8.2e}" for s in row))

    rr = refresh_tiles(A, report.scores, RefreshPolicy(threshold=0.01),
                       key=jax.random.fold_in(key, 14))
    restored = digital_rel(15)
    print(f"[lifetime] refreshed {len(rr.tiles)}/{report.scores.size} tiles "
          f"{list(rr.tiles)}: solve {restored:.2e}, "
          f"energy {float(rr.write_stats.energy_j):.3e} J vs full reprogram "
          f"{float(rr.full_rewrite_stats.energy_j):.3e} J "
          f"({rr.energy_saving:.0%} saved)")
    assert restored <= 2.0 * fresh, (restored, fresh)
    assert float(rr.write_stats.energy_j) \
        < float(rr.full_rewrite_stats.energy_j)


def fault_act(n: int, mesh_shape) -> None:
    mesh = make_mesh(mesh_shape, ("data", "model"))
    key = jax.random.PRNGKey(2)
    a, b = _spd(n, key)
    cfg = CrossbarConfig(device=get_device("epiram"),
                         geom=MCAGeometry(2, 2, 16, 16), k_iters=5, ec=True)
    engine = AnalogEngine(cfg, execution="distributed", mesh=mesh)
    A = engine.program(a, jax.random.fold_in(key, 7))

    state = {"saved": None}

    def inject(seg, h):
        if seg == 1 and state["saved"] is None:
            state["saved"] = h.at_dense
            dense = np.array(jax.device_get(h.at_dense))
            dense[:, 5] = np.max(np.abs(dense))  # column stuck at G_on rail
            h.at_dense = jax.device_put(jnp.asarray(dense),
                                        h.at_dense.sharding)
            print("[fault]    segment 1: column 5 latched at the G_on rail")

    def repair(event, h):
        h.at_dense = state["saved"]
        print(f"[fault]    detected ({event.kind}, digital residual "
              f"{event.residual:.2e}) -> rolled back to checkpoint step "
              f"{event.restored_step}, operator repaired")

    res = ft_cg(A, b, tol=1e-4, maxiter=400, segment=25,
                key=jax.random.fold_in(key, 9), segment_hook=inject,
                on_fault=repair)
    print(f"[fault]    converged={res.converged} after {res.iterations} "
          f"accepted segments, {res.restores} restore(s), final digital "
          f"residual {res.final_residual:.2e} on "
          f"{jax.device_count()} devices")
    assert res.converged and res.restores >= 1, (res.converged, res.restores)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=256)
    # ag-si: the highest fault-rate device in the zoo -- damage shows up in
    # few MVMs, which keeps the example quick (sweep the rest via
    # benchmarks/reliability.py).
    ap.add_argument("--device", default="ag-si")
    ap.add_argument("--mesh", default="2,4", metavar="R,C")
    args = ap.parse_args()
    try:
        rows, cols = (int(v) for v in args.mesh.split(","))
    except ValueError:
        raise SystemExit(f"--mesh must be 'R,C' integers, got {args.mesh!r}")
    if rows * cols > jax.device_count():
        raise SystemExit(
            f"--mesh {rows}x{cols} needs {rows * cols} devices but only "
            f"{jax.device_count()} are available")

    lifetime_act(args.n, args.device)
    print()
    fault_act(min(args.n, 128), (rows, cols))


if __name__ == "__main__":
    main()
