"""Device-lifetime reliability: aging x refresh policy x fault-tolerant solves.

Three row families over :mod:`repro.reliability`:

  * **aging + refresh policy** -- per device model, one programmed SPD image
    is aged by the device's own read-disturb fault process (MVM count tuned
    so ~8 cells latch), then solved under three refresh policies: ``none``
    (solve the damaged image), ``tiles`` (probe, re-program only the tiles
    above threshold), ``full`` (re-program everything).  Rows report the
    DIGITAL solve residual ||b - A x|| / ||b|| (the recursive analog residual
    lies on a damaged operator) and the actual write-verify energy.
  * **fault-tolerant solves** -- :func:`repro.reliability.ft_cg` with a
    stuck-column fault injected mid-solve, in-process on a local handle and
    in a subprocess on a 2x4 device mesh (distributed dense execution, the
    host-side ``at_dense`` injection path).
  * **serving refresh scheduling** -- the :mod:`repro.serving` simulator with
    and without a :class:`~repro.serving.ReliabilityConfig`, trading refresh
    stalls/energy against the predicted residual images are served at.

Acceptance contracts asserted by ``main()``:

  A. the unrefreshed aged solve residual exceeds tolerance, and
     tile-selective refresh restores the solve to within 2x the fresh-image
     residual at STRICTLY less write energy than full reprogramming;
  B. a mid-solve injected stuck-at fault in distributed CG is detected and
     recovered through CheckpointManager to ``converged=True`` on a 2x4 mesh.

Results land in ``BENCH_reliability.json`` (full runs refresh the checked-in
baseline; smoke/quick runs write to the temp dir), stamped with
``run_metadata()``.

    PYTHONPATH=src python -m benchmarks.reliability            # quick
    PYTHONPATH=src python -m benchmarks.reliability --smoke    # CI
    PYTHONPATH=src python -m benchmarks.reliability --full
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import textwrap
from typing import Dict, List

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import CrossbarConfig, MCAGeometry, get_device
from repro.engine import AnalogEngine
from repro.reliability import (RefreshPolicy, attach_age, ft_cg,
                               predicted_residual, probe_tile_scores,
                               refresh_tiles)
from repro.solvers import cg

from .common import run_metadata

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_JSON = os.path.join(REPO, "BENCH_reliability.json")

DEVICES_SMOKE = ["epiram"]
DEVICES_QUICK = ["epiram", "taox-hfox"]
DEVICES_FULL = ["epiram", "ag-si", "alox-hfo2", "taox-hfox"]

#: aged-solve digital residual above this counts as "image needs refresh"
AGED_TOL = 1e-2
#: expected number of latched cells the aging scenario targets
TARGET_FAULTS = 8.0


def _spd_system(n: int, key: jax.Array):
    r = jax.random.normal(key, (n, n), jnp.float32) / n
    a = r + r.T + 2.0 * jnp.eye(n, dtype=jnp.float32)
    x_true = jax.random.normal(jax.random.fold_in(key, 1), (n,), jnp.float32)
    return a, x_true, a @ x_true


def _aging_mvms(device, n: int) -> int:
    """MVM count at which ~TARGET_FAULTS cells of an n x n image latch."""
    return max(1, int(TARGET_FAULTS / (device.fault_rate * n * n)))


def _aging_rows(device_name: str, n: int) -> List[Dict]:
    """One device's aging scenario under the none/tiles/full refresh menu."""
    key = jax.random.PRNGKey(0)
    a, _x_true, b = _spd_system(n, key)
    bn = float(jnp.linalg.norm(b))
    dev = get_device(device_name)
    cfg = CrossbarConfig(device=dev, geom=MCAGeometry(2, 2, 32, 32),
                         k_iters=5, ec=True)
    mvms = _aging_mvms(dev, n)

    def fresh_aged_handle(salt: int):
        engine = AnalogEngine(cfg)
        A = engine.program(a, jax.random.fold_in(key, 7))
        attach_age(A)
        res = cg(A, b, tol=1e-6, maxiter=120,
                 key=jax.random.fold_in(key, salt))
        fresh_rel = float(jnp.linalg.norm(b - a @ res.x)) / bn
        A.age = A.age.advanced(mvms)
        return A, fresh_rel

    def digital_rel(A, salt: int) -> float:
        res = cg(A, b, tol=1e-6, maxiter=120,
                 key=jax.random.fold_in(key, salt))
        return float(jnp.linalg.norm(b - a @ res.x)) / bn

    rows: List[Dict] = []
    pred = predicted_residual(dev, k_iters=cfg.k_iters, seconds=0.0,
                              mvms=mvms, n=n)

    # none: solve the damaged image as-is
    A, fresh_rel = fresh_aged_handle(11)
    aged_rel = digital_rel(A, 12)
    rows.append({"name": f"reliability/age/{device_name}/none",
                 "fresh_rel": f"{fresh_rel:.3e}",
                 "solve_rel": f"{aged_rel:.3e}",
                 "predicted": f"{pred:.3e}", "aged_mvms": mvms,
                 "refresh_energy_j": 0.0, "tiles_refreshed": 0})

    # tiles: probe, re-program only the flagged tiles
    report = probe_tile_scores(A, key=jax.random.fold_in(key, 13))
    rr = refresh_tiles(A, report.scores, RefreshPolicy(threshold=0.01),
                       key=jax.random.fold_in(key, 14))
    tiles_rel = digital_rel(A, 15)
    rows.append({"name": f"reliability/age/{device_name}/tiles",
                 "fresh_rel": f"{fresh_rel:.3e}",
                 "solve_rel": f"{tiles_rel:.3e}",
                 "probe_worst": f"{report.worst:.3e}",
                 "refresh_energy_j": float(rr.write_stats.energy_j),
                 "full_rewrite_j": float(rr.full_rewrite_stats.energy_j),
                 "energy_saving": round(rr.energy_saving, 3),
                 "tiles_refreshed": len(rr.tiles),
                 "tiles_total": int(report.scores.size)})

    # full: re-program every tile (threshold below any score selects all)
    A2, _ = fresh_aged_handle(11)
    report2 = probe_tile_scores(A2, key=jax.random.fold_in(key, 13))
    rr2 = refresh_tiles(A2, report2.scores, RefreshPolicy(threshold=-1.0),
                        key=jax.random.fold_in(key, 14))
    full_rel = digital_rel(A2, 15)
    rows.append({"name": f"reliability/age/{device_name}/full",
                 "fresh_rel": f"{fresh_rel:.3e}",
                 "solve_rel": f"{full_rel:.3e}",
                 "refresh_energy_j": float(rr2.write_stats.energy_j),
                 "tiles_refreshed": len(rr2.tiles)})
    return rows


def _ft_local_row(n: int) -> Dict:
    """In-process fault-tolerant CG: stuck column injected at segment 1 on a
    local handle, repaired by the ``on_fault`` callback."""
    key = jax.random.PRNGKey(2)
    a, _x_true, b = _spd_system(n, key)
    cfg = CrossbarConfig(device=get_device("epiram"),
                         geom=MCAGeometry(2, 2, 32, 32), k_iters=5, ec=True)
    A = AnalogEngine(cfg).program(a, jax.random.fold_in(key, 7))

    state = {"saved": None}

    def inject(seg, h):
        if seg == 1 and state["saved"] is None:
            state["saved"] = h.at_blocks
            blocks = np.array(jax.device_get(h.at_blocks))
            # full physical column stuck at the G_on rail (both row blocks)
            blocks[:, 0, :, 3] = np.max(np.abs(blocks))
            h.at_blocks = jnp.asarray(blocks)
            h.release()

    def repair(event, h):
        h.at_blocks = state["saved"]
        h.release()

    res = ft_cg(A, b, tol=1e-4, maxiter=400, segment=25,
                key=jax.random.fold_in(key, 9), segment_hook=inject,
                on_fault=repair)
    return {"name": "reliability/ft/cg/local",
            "converged": bool(res.converged),
            "restores": int(res.restores),
            "segments": int(res.iterations),
            "final_rel": f"{res.final_residual:.3e}",
            "events": ";".join(e.kind for e in res.fault_events)}


_DISTRIBUTED_CHILD = textwrap.dedent("""
    import json
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from repro.core import CrossbarConfig, MCAGeometry, get_device
    from repro.engine import AnalogEngine
    from repro.reliability import ft_cg

    mesh = make_mesh((2, 4), ("data", "model"))
    n = {n}
    key = jax.random.PRNGKey(0)
    r = jax.random.normal(key, (n, n), jnp.float32) / n
    a = r + r.T + 2.0 * jnp.eye(n, dtype=jnp.float32)
    x_true = jax.random.normal(jax.random.fold_in(key, 1), (n,), jnp.float32)
    b = a @ x_true

    cfg = CrossbarConfig(device=get_device("epiram"),
                         geom=MCAGeometry(2, 2, 16, 16), k_iters=5, ec=True)
    engine = AnalogEngine(cfg, execution="distributed", mesh=mesh)
    A = engine.program(a, jax.random.fold_in(key, 7))

    state = {{"saved": None}}

    def inject(seg, h):
        if seg == 1 and state["saved"] is None:
            state["saved"] = h.at_dense
            dense = np.array(jax.device_get(h.at_dense))
            dense[:, 5] = np.max(np.abs(dense))    # column stuck at G_on rail
            h.at_dense = jax.device_put(jnp.asarray(dense),
                                        h.at_dense.sharding)

    def repair(event, h):
        h.at_dense = state["saved"]

    res = ft_cg(A, b, tol=1e-4, maxiter=400, segment=25,
                key=jax.random.fold_in(key, 9), segment_hook=inject,
                on_fault=repair)
    print(json.dumps({{
        "converged": bool(res.converged), "restores": int(res.restores),
        "segments": int(res.iterations),
        "final_rel": float(res.final_residual),
        "events": [e.kind for e in res.fault_events],
        "devices": jax.device_count(), "backend": jax.default_backend()}}))
""")


def _ft_distributed_row(n: int) -> Dict:
    """Contract B in a subprocess: 8 virtual host devices, 2x4 mesh, a fault
    injected into the sharded dense image mid-solve.  The child always runs
    on the CPU backend (a parent that has touched JAX holds the chip), and
    its row says so in ``backend``."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c",
                          _DISTRIBUTED_CHILD.format(n=n)],
                         capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, f"child failed:\n{out.stdout}\n{out.stderr}"
    res = json.loads(out.stdout.splitlines()[-1])
    return {"name": "reliability/ft/cg/distributed-2x4",
            "converged": bool(res["converged"]),
            "restores": int(res["restores"]),
            "segments": int(res["segments"]),
            "final_rel": f"{res['final_rel']:.3e}",
            "events": ";".join(res["events"]),
            "devices": int(res["devices"]),
            "backend": res["backend"]}


def _serving_rows(n_requests: int) -> List[Dict]:
    """Refresh scheduling vs traffic: the simulator with and without the
    reliability controller, on the fast-drifting ag-si device."""
    from repro.configs.base import RRAMBackendConfig
    from repro.serving import (ReliabilityConfig, ServingConfig, TenantSpec,
                               TrafficConfig, simulate)
    tenants = (TenantSpec("acme", "zamba2-1.2b"),
               TenantSpec("globex", "zamba2-1.2b"))
    traffic = TrafficConfig(n_requests=n_requests, rate_rps=4.0, seed=3)
    rram = RRAMBackendConfig(enabled=True, device="ag-si", k_iters=3)
    rows: List[Dict] = []
    for label, rel in (("off", None),
                       ("thr-0.05", ReliabilityConfig(refresh_threshold=0.05)),
                       ("thr-1.0", ReliabilityConfig(refresh_threshold=1.0))):
        res = simulate(ServingConfig(tenants=tenants, traffic=traffic,
                                     rram=rram, run_model=False,
                                     seed=0, reliability=rel))
        row = {"name": f"reliability/serving/{label}",
               "joules_per_token": f"{res.summary['joules_per_token']:.3e}",
               "p99_latency_s": round(res.summary["p99_latency_s"], 3)}
        rel_sum = res.summary.get("reliability")
        if rel_sum is not None:
            row.update({
                "refreshes": rel_sum["refreshes"],
                "refresh_energy_j": f"{rel_sum['refresh_energy_j']:.3e}",
                "refresh_stall_s": round(rel_sum["refresh_stall_s"], 2),
                "mean_predicted": f"{rel_sum['mean_predicted_residual']:.3e}",
                "max_predicted": f"{rel_sum['max_predicted_residual']:.3e}"})
        rows.append(row)
    return rows


def run(quick: bool = True, smoke: bool = False) -> List[Dict]:
    n = 256
    devices = DEVICES_SMOKE if smoke else (DEVICES_QUICK if quick
                                           else DEVICES_FULL)
    rows: List[Dict] = []
    for dev in devices:
        rows.extend(_aging_rows(dev, n))
    rows.append(_ft_local_row(128 if smoke else n))
    rows.append(_ft_distributed_row(128))
    if not smoke:
        rows.extend(_serving_rows(16 if quick else 48))
    _write_json(rows, quick or smoke,
                "smoke" if smoke else ("quick" if quick else "full"))
    return rows


def _out_path(quick: bool) -> str:
    if quick:
        return os.path.join(tempfile.gettempdir(),
                            "BENCH_reliability.smoke.json")
    return OUT_JSON


def _write_json(rows: List[Dict], quick: bool, mode: str) -> str:
    payload = {
        "bench": "reliability",
        "mode": mode,
        "metadata": run_metadata(),
        "rows": rows,
    }
    out = _out_path(quick)
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    return out


def _assert_contracts(rows: List[Dict]) -> None:
    # Contract A: unrefreshed aged solve misses tolerance; tile-selective
    # refresh restores it to within 2x fresh at strictly less write energy
    # than a full reprogram.
    none_row = next(r for r in rows
                    if r["name"] == "reliability/age/epiram/none")
    tiles_row = next(r for r in rows
                     if r["name"] == "reliability/age/epiram/tiles")
    fresh = float(tiles_row["fresh_rel"])
    assert float(none_row["solve_rel"]) > AGED_TOL, none_row
    assert float(tiles_row["solve_rel"]) <= 2.0 * fresh, (tiles_row, fresh)
    assert tiles_row["refresh_energy_j"] < tiles_row["full_rewrite_j"], \
        tiles_row
    assert 0 < tiles_row["tiles_refreshed"] < tiles_row["tiles_total"], \
        tiles_row
    # Contract B: the distributed mid-solve fault is detected and recovered
    # through CheckpointManager to a converged solve.
    dist = next(r for r in rows
                if r["name"] == "reliability/ft/cg/distributed-2x4")
    assert dist["converged"] and dist["restores"] >= 1, dist
    assert dist["devices"] == 8, dist


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="one device, no serving sweep (CI fast job); writes "
                         "to the temp dir")
    ap.add_argument("--full", action="store_true",
                    help="all four devices + serving sweep; refreshes the "
                         "checked-in JSON")
    args = ap.parse_args()
    rows = run(quick=not args.full, smoke=args.smoke)
    for r in rows:
        detail = ", ".join(f"{k}={v}" for k, v in r.items() if k != "name")
        print(f"{r['name']}: {detail}")
    print(f"wrote {_out_path(not args.full)}")
    _assert_contracts(rows)
    print("contracts A (tile refresh) and B (distributed recovery): OK")


if __name__ == "__main__":
    main()
