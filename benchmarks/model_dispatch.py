"""Whole-model dispatch: grouped single-launch forward vs per-layer loop.

The tentpole claim of the grouped-execution PR (DESIGN.md section 13): a
whole analog model forward -- L same-geometry layers -- executes as ONE
device dispatch through :class:`~repro.engine.AnalogMatrixGroup` instead of
L per-layer dispatches.  This benchmark sweeps layers-per-group x arch shape
and reports, for identical per-member keys:

  * ``chain``   -- L square layers chained activation-to-logits through
    ``engine.chain_mvm`` (ONE ``lax.scan`` dispatch) vs a Python loop of L
    solo ``engine.mvm`` calls with the same relu between layers;
  * ``experts`` -- L parallel expert kernels (the MoE pattern) executed by
    one grouped broadcast MVM vs L solo MVMs;
  * dispatch counts for both paths (grouped is 1 by construction -- the
    DispatchCount invariant pins it -- per-layer is L), their ratio, and
    grouped-vs-solo parity (``rel_l2``).

Results land in ``BENCH_model_dispatch.json`` at the repo root (checked in;
``tools/check_perf.py`` gates dispatch counts against it).

    PYTHONPATH=src python -m benchmarks.model_dispatch            # full sweep
    PYTHONPATH=src python -m benchmarks.model_dispatch --smoke    # CI fast job
"""
from __future__ import annotations

import argparse
import json
import os
import tempfile
from typing import Dict, List

import jax
import jax.numpy as jnp

from repro.core import CrossbarConfig, MCAGeometry, get_device, rel_l2
from repro.engine import AnalogEngine

from .common import run_metadata

CAP = 32                                   # capacity block edge (1x1 tile MCA)
GEOM = MCAGeometry(tile_rows=1, tile_cols=1, cell_rows=CAP, cell_cols=CAP)
LAYERS_FULL = [2, 4, 8, 16]
LAYERS_SMOKE = [2, 8]
ARCHS_FULL = {"mlp128": 128, "mlp256": 256}     # layer width d (square d x d)
ARCHS_SMOKE = {"mlp128": 128}
OUT_JSON = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_model_dispatch.json")


def _solo_handles(engine: AnalogEngine, stack: jnp.ndarray, key: jax.Array):
    """Per-layer handles under the group's member keys (fold g of key)."""
    return [engine.program(stack[g], jax.random.fold_in(key, g))
            for g in range(stack.shape[0])]


def _bench_chain(arch: str, d: int, L: int, cfg: CrossbarConfig) -> Dict:
    """Whole-model forward: L chained square layers, relu between members."""
    key = jax.random.fold_in(jax.random.PRNGKey(13), d * 1000 + L)
    stack = jax.random.normal(key, (L, d, d), jnp.float32) / float(d)
    x = jax.random.normal(jax.random.fold_in(key, 1), (d,))
    k_mvm = jax.random.fold_in(key, 2)

    engine = AnalogEngine(cfg)
    G = engine.program_group(stack, key)
    handles = _solo_handles(engine, stack, key)

    def solo_forward():
        h = x
        for g, A in enumerate(handles):
            h = jax.nn.relu(engine.mvm(A, h, key=jax.random.fold_in(k_mvm, g)))
        return h

    y_group = engine.chain_mvm(G, x, key=k_mvm, activation="relu")
    y_solo = solo_forward()
    return _row("chain", arch, d, L, float(rel_l2(y_group, y_solo)))


def _bench_experts(arch: str, d: int, L: int, cfg: CrossbarConfig) -> Dict:
    """MoE pattern: L parallel expert kernels, one broadcast input."""
    key = jax.random.fold_in(jax.random.PRNGKey(17), d * 1000 + L)
    stack = jax.random.normal(key, (L, d, d), jnp.float32) / float(d)
    x = jax.random.normal(jax.random.fold_in(key, 1), (d,))
    k_mvm = jax.random.fold_in(key, 2)

    engine = AnalogEngine(cfg)
    G = engine.program_group(stack, key)
    handles = _solo_handles(engine, stack, key)

    def solo_experts():
        return jnp.stack([
            engine.mvm(A, x, key=jax.random.fold_in(k_mvm, g))
            for g, A in enumerate(handles)])

    y_group = engine.group_mvm(G, x, key=k_mvm)
    y_solo = solo_experts()
    return _row("experts", arch, d, L, float(rel_l2(y_group, y_solo)))


def _row(mode: str, arch: str, d: int, L: int, parity: float) -> Dict:
    return {
        "name": f"model_dispatch/{mode}/{arch}/L{L}",
        "layers": L,
        "width": d,
        "dispatches_group": 1,
        "dispatches_solo": L,
        "dispatch_reduction": L,
        "rel_l2_group_vs_solo": parity,
    }


def run(quick: bool = True) -> List[Dict]:
    cfg = CrossbarConfig(device=get_device("taox-hfox"), geom=GEOM,
                         k_iters=5, ec=True)
    layers = LAYERS_SMOKE if quick else LAYERS_FULL
    archs = ARCHS_SMOKE if quick else ARCHS_FULL
    rows: List[Dict] = []
    for arch, d in archs.items():
        for L in layers:
            rows.append(_bench_chain(arch, d, L, cfg))
            rows.append(_bench_experts(arch, d, L, cfg))
    _write_json(rows, quick)
    return rows


def _out_path(quick: bool) -> str:
    """Full sweeps refresh the checked-in baseline at the repo root; smoke
    runs (CI, ``benchmarks.run`` default) write to the temp dir."""
    if quick:
        return os.path.join(tempfile.gettempdir(),
                            "BENCH_model_dispatch.smoke.json")
    return OUT_JSON


def _write_json(rows: List[Dict], quick: bool) -> str:
    payload = {
        "bench": "model_dispatch",
        "mode": "smoke" if quick else "full",
        "metadata": run_metadata(),
        "geom": {"cap": CAP, "tiles": [1, 1]},
        "rows": rows,
    }
    out = _out_path(quick)
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="small sweep (CI fast job); "
                         "writes to the temp dir, leaving the checked-in "
                         "full-sweep JSON untouched")
    args = ap.parse_args()
    rows = run(quick=args.smoke)
    for r in rows:
        print(f"{r['name']}: {r['dispatch_reduction']}x dispatches, "
              f"parity {r['rel_l2_group_vs_solo']:.2e}")
    print(f"wrote {_out_path(args.smoke)}")
    # Acceptance contract: grouped execution cuts dispatches >= 5x once a
    # group holds >= 8 layers, and grouped-vs-solo parity stays <= 1e-5.
    deep = [r for r in rows if r["layers"] >= 8]
    assert deep, "sweep must include a >=8-layer group"
    assert all(r["dispatches_solo"] / r["dispatches_group"] >= 5
               for r in deep), deep
    assert all(r["rel_l2_group_vs_solo"] <= 1e-5 for r in rows), rows


if __name__ == "__main__":
    main()
