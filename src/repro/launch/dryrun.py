import os
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_DRYRUN_XLA_FLAGS")
                           or "--xla_force_host_platform_device_count=512")
# ^ MUST precede every other import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile EVERY (architecture x input-shape) cell
on the production meshes and record memory / cost / collective statistics.

    PYTHONPATH=src python -m repro.launch.dryrun                 # all cells
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --multi-pod     # 2x16x16
    PYTHONPATH=src python -m repro.launch.dryrun --cell meliso   # paper MVM

Results are cached one JSON per cell under experiments/dryrun/ (re-runs skip
cached cells unless --force); EXPERIMENTS.md section Dry-run/Roofline is
generated from these files by analysis/report.py.
"""
import argparse
import json
import time
import traceback
from typing import Dict, Optional

import jax

import jax.numpy as jnp

from repro.analysis.model_flops import model_flops
from repro.analysis.roofline import analyze_compiled
from repro.configs import ARCHS, get_arch
from repro.configs.base import RRAMBackendConfig
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_cell

# The chip the production mesh is made of (the dry run compiles on host
# devices standing in for it).
MODELED_KIND = "TPU v5 lite"

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def cell_id(arch: str, shape: str, multi_pod: bool, tag: str = "") -> str:
    mesh = "pod2x16x16" if multi_pod else "16x16"
    suffix = f"_{tag}" if tag else ""
    return f"{arch}_{shape}_{mesh}{suffix}".replace("/", "-")


def run_lm_cell(arch_name: str, shape_name: str, multi_pod: bool,
                rram: bool = False, runtime_kw: Optional[Dict] = None,
                dump_hlo: Optional[str] = None,
                micro: Optional[int] = None) -> Dict:
    arch = get_arch(arch_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    rcfg = RRAMBackendConfig(enabled=True) if rram else None
    tcfg = None
    if micro:
        from repro.configs.base import TrainConfig
        tcfg = TrainConfig(microbatch=micro, remat="block")
    cell = build_cell(arch, shape_name, mesh, rram=rcfg,
                      runtime_kw=runtime_kw, tcfg=tcfg)

    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
        jitted = jax.jit(cell.fn,
                         in_shardings=cell.in_shardings,
                         out_shardings=cell.out_shardings,
                         donate_argnums=cell.donate)
        lowered = jitted.lower(*cell.args)
        t_lower = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0

    mf = model_flops(arch, shape_name)
    rec = analyze_compiled(compiled, mesh.size, MODELED_KIND,
                           model_flops=mf["model_flops"])
    rec.update({
        "arch": arch_name, "shape": shape_name,
        "mesh": list(mesh.devices.shape), "axes": list(mesh.axis_names),
        "kind": cell.meta["kind"], "rram": rram,
        "params": mf["params"], "active_params": mf["active_params"],
        "lower_s": t_lower, "compile_s": t_compile,
        "runtime_kw": {k: str(v) for k, v in (runtime_kw or {}).items()},
    })
    print(compiled.memory_analysis())
    print({k: v for k, v in (compiled.cost_analysis() or {}).items()
           if k in ("flops", "bytes accessed")})
    if dump_hlo:
        with open(dump_hlo, "w") as f:
            f.write(compiled.as_text())
    return rec


def run_meliso_cell(multi_pod: bool, n: int = 65536,
                    ec: bool = True, ec_mode: str = "fused",
                    denoise: str = "neumann", cell_size: int = 512,
                    dump_hlo: Optional[str] = None,
                    prng: str = "threefry") -> Dict:
    """The paper's own workload: distributed two-tier-EC MVM at 65,536^2."""
    from repro.core import CrossbarConfig, MCAGeometry, get_device
    from repro.core.distributed import (make_distributed_program,
                                        make_distributed_programmed_mvm)

    mesh = make_production_mesh(multi_pod=multi_pod)
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    row_axes = tuple(a for a in ("pod", "data") if a in axes)
    rows_div = 1
    for a in row_axes:
        rows_div *= axes[a]
    local_m, local_n = n // rows_div, n // axes["model"]
    geom = MCAGeometry(tile_rows=max(local_m // cell_size, 1),
                       tile_cols=max(local_n // cell_size, 1),
                       cell_rows=cell_size, cell_cols=cell_size)
    ccfg = CrossbarConfig(device=get_device("taox-hfox"), geom=geom,
                          k_iters=5, ec=ec, ec_mode=ec_mode,
                          denoise_method=denoise)
    # Lower the full program+execute pipeline (the one-shot serving shape).
    program = make_distributed_program(ccfg, mesh, row_axes, "model")
    execute = make_distributed_programmed_mvm(
        ccfg, mesh, row_axes, "model", stats_include_matrix=True)

    def fn(a, x, key):
        at, da, _ = program(a, key)
        return execute(at, da, x, key)

    a_abs = jax.ShapeDtypeStruct((n, n), jnp.float32)
    x_abs = jax.ShapeDtypeStruct((n, 1), jnp.float32)
    # prng="rbg": hardware rng-bit-generator -- one pass, no threefry counter
    # arrays (EXPERIMENTS.md Perf M2); threefry is the reproducible default.
    # "threefry" is accepted as an alias; jax registers it as "threefry2x32".
    impl = {"threefry": "threefry2x32"}.get(prng, prng)
    key_abs = jax.eval_shape(lambda: jax.random.key(0, impl=impl))

    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
        lowered = jax.jit(fn).lower(a_abs, x_abs, key_abs)
        t_lower = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0

    # Useful compute: tier-1 EC = 2 matmuls (fused) or 3 (faithful) + denoise.
    mm = 2 if (ec and ec_mode == "fused") else (3 if ec else 1)
    useful = 2.0 * n * n * mm
    rec = analyze_compiled(compiled, mesh.size, MODELED_KIND,
                           model_flops=useful)
    rec.update({
        "arch": "meliso-mvm", "shape": f"mvm_{n}",
        "mesh": list(mesh.devices.shape), "axes": list(mesh.axis_names),
        "kind": "mvm", "ec": ec, "ec_mode": ec_mode, "denoise": denoise,
        "cell_size": cell_size, "prng": prng,
        "lower_s": t_lower, "compile_s": t_compile,
    })
    print(compiled.memory_analysis())
    if dump_hlo:
        with open(dump_hlo, "w") as f:
            f.write(compiled.as_text())
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--cell", default=None, choices=[None, "meliso"],
                    help="special non-LM cells")
    ap.add_argument("--rram", action="store_true",
                    help="lower the serve step on the analog RRAM backend")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--dump-hlo", default=None)
    ap.add_argument("--runtime-kw", default=None,
                    help="JSON dict of Runtime overrides (perf experiments)")
    ap.add_argument("--micro", type=int, default=None,
                    help="global microbatch override (perf experiments)")
    ap.add_argument("--prng", default="threefry",
                    help="meliso cell PRNG impl (threefry | rbg)")
    ap.add_argument("--ec-mode", default="fused", choices=["fused", "faithful"])
    args = ap.parse_args()

    os.makedirs(OUT_DIR, exist_ok=True)
    meshes = ([False, True] if args.both_meshes
              else [args.multi_pod])
    runtime_kw = json.loads(args.runtime_kw) if args.runtime_kw else None

    if args.cell == "meliso":
        for mp in meshes:
            cid = cell_id("meliso-mvm", "mvm_65k", mp, args.tag)
            path = os.path.join(OUT_DIR, cid + ".json")
            if os.path.exists(path) and not args.force:
                print(f"[cached] {cid}")
                continue
            print(f"[run] {cid}")
            rec = run_meliso_cell(mp, dump_hlo=args.dump_hlo, prng=args.prng,
                                  ec_mode=args.ec_mode)
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
        return

    archs = [args.arch] if args.arch else list(ARCHS)
    n_ok = n_fail = 0
    for arch_name in archs:
        arch = get_arch(arch_name)
        shapes = [args.shape] if args.shape else list(arch.shapes)
        for shape_name in shapes:
            if shape_name not in arch.shapes:
                print(f"[skip] {arch_name} x {shape_name}: "
                      f"{dict(arch.skip_reasons).get(shape_name, 'not in arch.shapes')}")
                continue
            for mp in meshes:
                tag = (args.tag + ("_rram" if args.rram else "")).strip("_")
                cid = cell_id(arch_name, shape_name, mp, tag)
                path = os.path.join(OUT_DIR, cid + ".json")
                if os.path.exists(path) and not args.force:
                    print(f"[cached] {cid}")
                    continue
                print(f"[run] {cid}", flush=True)
                try:
                    rec = run_lm_cell(arch_name, shape_name, mp,
                                      rram=args.rram, runtime_kw=runtime_kw,
                                      dump_hlo=args.dump_hlo, micro=args.micro)
                    with open(path, "w") as f:
                        json.dump(rec, f, indent=1)
                    print(f"[ok] {cid}: dominant={rec['dominant']} "
                          f"compute={rec['compute_s']:.3e}s "
                          f"mem={rec['memory_s']:.3e}s "
                          f"coll={rec['collective_s']:.3e}s "
                          f"fits={rec['memory']['fits_hbm']}", flush=True)
                    n_ok += 1
                except Exception:
                    n_fail += 1
                    err = traceback.format_exc()
                    print(f"[FAIL] {cid}\n{err}", flush=True)
                    with open(path + ".err", "w") as f:
                        f.write(err)
    print(f"dryrun complete: {n_ok} ok, {n_fail} failed")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
