"""Chip smoke test: the system's main paths, once each, on a TPU.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: the 2x2 mesh phase only

Phases (each prints one line; any failed check raises, and the script exits
non-zero without printing a result):

  device    refuses anything but a TPU; prints its kind, count and memory.
  accuracy  n=2048 corrected MVM, epiram and taox-hfox, raw and with EC,
            reference and pallas backends, against the digital product at
            HIGHEST precision.
  paper     the meliso-mvm configuration: n=65,536, 2,048^2 capacity blocks,
            the ImplicitBandedMatrix producer on a 1x1-mesh distributed engine
            with resident=False; one MVM, then CG (tol 5e-3, 12 iterations).
  resident  execution="local", backend="pallas" at the largest n whose image
            fits: MVMs at batch 1 and 64 and a pallas CG solve, all through
            compiled (Mosaic) kernels.
  serving   qwen3-1.7b at its published widths (depth cut only where the
            printed byte reckoning says 16 GB cannot hold it), programmed
            through program_rram inside Server; 4 requests of 128
            prompt tokens and 16 new tokens; analog prefill logits against
            the digital model's at HIGHEST precision.

With ``--chips 4`` the paper-scale MVM and CG run on a 2x2 (data x model)
mesh and are compared with the same run on a 1x1 mesh over device 0.

The last line of standard output is ``{"ok": true, "device": {...}}``.
Timings printed are set-up times (compilation included), not performance.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# CPU values of the accuracy probe (rel_l2, reference backend), which the
# chip must reproduce within 1.5x for the EC rows.
CPU_PROBE = {("epiram", False): 2.75e-2, ("epiram", True): 3.6e-4,
             ("taox-hfox", False): 0.243, ("taox-hfox", True): 2.86e-2}
PROBE_SLACK = 1.5
# CPU rel_l2 of one epiram+EC MVM of the ImplicitBandedMatrix at 512^2 cells
# and 2,048^2 capacity blocks (largest of the distributed, resident batch-1
# and batch-64 runs at n=8192); the paper and resident phases allow 1.5x.
BANDED_CPU = 6.13e-4


class SmokeFailure(AssertionError):
    """A phase found a wrong or missing result."""


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def peak_bytes(dev) -> int:
    return int((dev.memory_stats() or {}).get("peak_bytes_in_use", -1))


def rel(a, b) -> float:
    from repro.core import rel_l2
    return float(rel_l2(a, b))


# --------------------------------------------------------------------- device
def phase_device(jax, chips: int):
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU found: JAX sees {len(devs)} "
            f"{d.platform} device(s) ({d.device_kind})")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} TPU "
                         f"devices; JAX sees {len(devs)}")
    limit = int((d.memory_stats() or {}).get("bytes_limit", 0))
    say("device", f"kind={d.device_kind} count={len(devs)} "
        f"bytes_limit={limit}")
    return d, limit


# ------------------------------------------------------------------- accuracy
def phase_accuracy(jax, jnp, n: int = 2048, cell: int = 512):
    from repro.core import CrossbarConfig, MCAGeometry, get_device
    from repro.engine import AnalogEngine
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (n, n)) / math.sqrt(n)
    x = jax.random.normal(jax.random.fold_in(key, 1), (n,))
    truth = jnp.matmul(a, x, precision=jax.lax.Precision.HIGHEST)
    geom = MCAGeometry(tile_rows=2, tile_cols=2, cell_rows=cell,
                       cell_cols=cell)
    errs = {}
    for backend in ("reference", "pallas"):
        for dev in ("epiram", "taox-hfox"):
            for ec in (False, True):
                cfg = CrossbarConfig(device=get_device(dev), geom=geom,
                                     k_iters=5, ec=ec)
                eng = AnalogEngine(cfg, backend=backend)
                A = eng.program(a, key)
                y = eng.mvm(A, x, key=jax.random.PRNGKey(3))
                err = rel(y, truth)
                errs[backend, dev, ec] = err
                say("accuracy", f"backend={backend} device={dev} "
                    f"ec={ec} rel_l2={err!r} cpu={CPU_PROBE[dev, ec]!r}")
    for backend in ("reference", "pallas"):
        for dev in ("epiram", "taox-hfox"):
            ec_err, raw_err = errs[backend, dev, True], errs[backend, dev, False]
            bound = PROBE_SLACK * CPU_PROBE[dev, True]
            check(ec_err <= bound, f"{backend}/{dev}+EC rel_l2 {ec_err} > "
                  f"{bound} (1.5x the CPU value)")
            check(ec_err < raw_err, f"{backend}/{dev}: EC {ec_err} not below "
                  f"raw {raw_err}")
    return errs


# ---------------------------------------------------------------- paper scale
def paper_setup(jax, n: int, cap: int, cell: int):
    """(cfg, producer matrix, key, right-hand side) of the meliso-mvm run."""
    from repro.core import CrossbarConfig, MCAGeometry, get_device
    from repro.core.matrices import ImplicitBandedMatrix
    import jax.numpy as jnp
    t = cap // cell
    geom = MCAGeometry(tile_rows=t, tile_cols=t, cell_rows=cell,
                       cell_cols=cell)
    cfg = CrossbarConfig(device=get_device("epiram"), geom=geom, k_iters=5,
                         ec=True)
    imp = ImplicitBandedMatrix(n=n, cap_m=cap, cap_n=cap, seed=n)
    key = jax.random.fold_in(jax.random.PRNGKey(5), n)
    return cfg, imp, key, jnp.ones((n,), jnp.float32)


def paper_run(jax, eng, imp, key, b, n: int):
    """One corrected MVM and one CG solve on a resident=False handle."""
    from repro import solvers
    A = eng.program(imp.block, key, shape=(n, n), resident=False)
    x = jax.random.normal(jax.random.fold_in(key, 7), (n,))
    y = eng.mvm(A, x, key=jax.random.fold_in(key, 8))
    res = solvers.cg(A, b, tol=5e-3, maxiter=12, key=key)
    return A, x, y, res


def phase_paper(jax, jnp, n: int = 65536, cap: int = 2048, cell: int = 512):
    from repro.analysis import verify as V
    from repro.engine import AnalogEngine
    from repro.launch.mesh import make_mesh
    cfg, imp, key, b = paper_setup(jax, n, cap, cell)
    mesh = make_mesh((1, 1), ("data", "model"),
                     devices=jax.devices()[:1])
    eng = AnalogEngine(cfg, execution="distributed", mesh=mesh)
    t0 = time.perf_counter()
    A, x, y, res = paper_run(jax, eng, imp, key, b, n)
    jax.block_until_ready((y, res.x))
    setup_s = time.perf_counter() - t0
    check(A.block_traceable and A.at_blocks is None,
          "the producer must trace (no host loop) and keep no image")
    spec = jax.ShapeDtypeStruct((n,), jnp.float32)
    kspec = jax.ShapeDtypeStruct(key.shape, key.dtype)
    disp = V.dispatch_count(V.trace(eng.mvm_fn(A), spec, kspec))
    dispatches = disp.summary["dispatch_boundaries"]
    check(dispatches == 1, f"the MVM is not one dispatch: {disp}")
    mvm_err = rel(y, imp.matvec(x))
    true_res = rel(imp.matvec(res.x), b)
    say("paper", f"n={n} cap={cap} mvm_rel_l2={mvm_err!r} "
        f"cg_iters={res.iterations} cg_converged={bool(res.converged)} "
        f"true_residual={true_res!r} dispatches_per_mvm={dispatches} "
        f"peak_bytes_in_use={peak_bytes(jax.devices()[0])} "
        f"setup_s={setup_s:.1f}")
    bound = PROBE_SLACK * BANDED_CPU
    check(mvm_err <= bound, f"paper MVM rel_l2 {mvm_err} > {bound}")
    check(bool(res.converged), "CG did not reach tol=5e-3 in 12 iterations")
    check(true_res <= 1e-2, f"CG true residual {true_res} > 1e-2")


# ------------------------------------------------------------------- resident
IMAGE_BYTES = 8       # at + da, float32 each, per matrix element
SOURCE_BYTES = 4      # the dense source matrix, alive while programming


def resident_n(limit: int, cap: int) -> int:
    """Largest capacity multiple n whose image plus the source matrix it is
    programmed from fit in 80% of the device memory (the phase peaked at
    12.03 B per element on a v5e at n=30,720)."""
    per_elem = IMAGE_BYTES + SOURCE_BYTES
    return max(1, int(math.sqrt(0.8 * limit / per_elem) // cap)) * cap


def phase_resident(jax, jnp, n: int, cap: int = 2048, cell: int = 512,
                   batch: int = 64, cg_tol: float = 1e-3):
    from repro import solvers
    from repro.core import crossbar
    from repro.engine import AnalogEngine
    from repro.kernels import on_cpu
    cfg, imp, key, b = paper_setup(jax, n, cap, cell)
    mb = n // cap
    gib = 2 ** 30
    say("resident", f"n={n} image at+da {IMAGE_BYTES} B/elt = "
        f"{IMAGE_BYTES * n * n / gib:.2f} GiB; source a {SOURCE_BYTES} B/elt"
        f" = {SOURCE_BYTES * n * n / gib:.2f} GiB (freed after programming);"
        f" transposed copies 0 (the kernel reads the block stack in place);"
        f" inputs x (n,{batch}) + x_tilde = "
        f"{2 * 4 * n * batch / gib:.3f} GiB")
    a = jax.jit(lambda: crossbar.assemble_blocks(
        crossbar.produce_blocks(imp.block, mb, mb), n, n))()
    xs = jax.random.normal(jax.random.fold_in(key, 9), (n, batch))
    hi = jax.lax.Precision.HIGHEST
    truth = jnp.matmul(a, xs, precision=hi)
    eng = AnalogEngine(cfg, backend="pallas")
    t0 = time.perf_counter()
    A = eng.program(a, key)
    del a
    k1, k64 = jax.random.fold_in(key, 1), jax.random.fold_in(key, 2)
    # The image goes in as arguments: a closed-over image would be lowered
    # as a constant of the program (host and device copies of 7+ GiB).
    lowered = jax.jit(lambda at, da, v, k: eng.mvm(
        dataclasses.replace(A, at_blocks=at, da_blocks=da), v, key=k)).lower(
            A.at_blocks, A.da_blocks, xs[:, 0], k1)
    check(on_cpu() or "tpu_custom_call" in lowered.as_text(),
          "the pallas MVM lowered without a Mosaic kernel")
    y1 = eng.mvm(A, xs[:, 0], key=k1)
    y64 = eng.mvm(A, xs, key=k64)
    res = solvers.cg(A, b, tol=cg_tol, maxiter=40, key=key, backend="pallas")
    jax.block_until_ready((y1, y64, res.x))
    setup_s = time.perf_counter() - t0
    e1, e64 = rel(y1, truth[:, 0]), rel(y64, truth)
    true_res = rel(imp.matvec(res.x[:, 0] if res.x.ndim == 2 else res.x), b)
    say("resident", f"n={n} mvm_b1_rel_l2={e1!r} mvm_b64_rel_l2={e64!r} "
        f"cg_iters={res.iterations} cg_converged={bool(res.converged)} "
        f"true_residual={true_res!r} "
        f"peak_bytes_in_use={peak_bytes(jax.devices()[0])} "
        f"setup_s={setup_s:.1f}")
    bound = PROBE_SLACK * BANDED_CPU
    check(e1 <= bound and e64 <= bound,
          f"resident MVM rel_l2 {e1}, {e64} > {bound}")
    check(bool(res.converged), f"pallas CG did not reach tol={cg_tol}")
    check(true_res <= 10 * cg_tol, f"pallas CG true residual {true_res}")


# -------------------------------------------------------------------- serving
# Bytes per linear parameter once programmed: w, w_tilde and dw in bf16.
PROGRAMMED_BYTES = 6
# Float32 temporaries per parameter while one kernel is programmed (the
# compiled encode of the 2048 x 151,936 head needs 2.52 GB of temporaries on
# a v5e: ~8 B per parameter).  Kernels are programmed one at a time, so only
# the largest kernel's temporaries count.
ENCODE_TEMP_BYTES = 8


def serving_depth(cfg, limit: int) -> tuple:
    """Deepest cut of ``cfg`` whose programmed model, plus the temporaries
    of programming its largest kernel (the lm_head), fits in 80% of the
    device memory; returns (layers, reckoning)."""
    d, f = cfg.d_model, cfg.d_ff
    attn = d * cfg.n_heads * cfg.d_head * 2 + d * cfg.n_kv_heads * cfg.d_head * 2
    layer = attn + 3 * d * f                        # linear params per layer
    vocab = cfg.vocab_pad * d
    fixed = (2 + PROGRAMMED_BYTES + ENCODE_TEMP_BYTES) * vocab
    per_layer = PROGRAMMED_BYTES * layer
    layers = int(min(cfg.n_layers, (0.8 * limit - fixed) // per_layer))
    gb = 1e9
    text = (f"linear params/layer={layer} x {PROGRAMMED_BYTES} B (w, w_tilde,"
            f" dw in bf16) = {per_layer / gb:.3f} GB/layer; embed (bf16) + "
            f"programmed lm_head + its encode temporaries "
            f"({ENCODE_TEMP_BYTES} B/param) = {fixed / gb:.3f} GB; "
            f"{layers} of {cfg.n_layers} layers fit 80% of "
            f"bytes_limit {limit / gb:.3f} GB")
    return layers, text


def logit_bound(eps: float, layers: int) -> float:
    """Bound on rel_l2(analog logits, digital logits).

    ``eps`` is the rel_l2 of one corrected product on this device (the
    accuracy probe's value).  Each layer feeds the residual stream through
    two branches of two products in series, and the head is one more
    product; independent per-product errors add in quadrature, so the
    logits carry at most eps * sqrt(4 * layers + 1): a branch's error is
    eps of the branch output, which is no larger than the stream it joins.
    """
    return eps * math.sqrt(4 * layers + 1)


def phase_serving(jax, jnp, arch_name: str = "qwen3-1.7b", layers=None,
                  limit: int = 0, eps: float = 2.86e-2, prompts: int = 4,
                  prompt_len: int = 128, new_tokens: int = 16,
                  cell: int = 512):
    from repro.configs import get_arch, model_module
    from repro.configs.base import RRAMBackendConfig
    from repro.core import rel_l2
    from repro.models import params as PM
    from repro.models.common import Runtime
    from repro.models.rram import strip_rram
    from repro.train.serve import Server
    model = get_arch(arch_name).model
    if layers is None:
        layers, text = serving_depth(model, limit)
        say("serving", f"depth cut: {text}")
    check(layers >= 1, "not one layer of the model fits")
    cfg = dataclasses.replace(model, n_layers=layers,
                              compute_dtype="float32")
    mod = model_module(cfg)
    params = PM.materialize(mod.init_specs(cfg), jax.random.PRNGKey(0),
                            dtype=jnp.dtype(cfg.param_dtype))
    rram = RRAMBackendConfig(enabled=True, cell_rows=cell, cell_cols=cell)
    t0 = time.perf_counter()
    max_len = prompt_len + new_tokens + 8
    srv = Server(mod, cfg, params, rt=Runtime(rram=rram),
                 max_len=max_len, key=jax.random.PRNGKey(9))
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (prompts, prompt_len), 0, cfg.vocab)}
    out = srv.generate(batch, new_tokens)
    out = jax.block_until_ready(out)
    setup_s = time.perf_counter() - t0
    check(out.shape == (prompts, new_tokens), f"generated shape {out.shape}")
    check(bool(jnp.all((out >= 0) & (out < cfg.vocab_pad))),
          "generated token ids out of range")
    key = jax.random.PRNGKey(11)
    analog, _ = jax.jit(lambda p, bt: mod.prefill(
        p, bt, cfg, Runtime(rram=rram, key=key), max_len))(srv.params, batch)
    digital, _ = jax.jit(lambda p, bt: mod.prefill(
        p, bt, cfg, Runtime(), max_len))(strip_rram(srv.params), batch)
    # Padded vocab columns hold a -1e30 mask in both; compare real tokens.
    analog, digital = analog[..., :cfg.vocab], digital[..., :cfg.vocab]
    err = float(rel_l2(analog.astype(jnp.float32),
                       digital.astype(jnp.float32)))
    bound = logit_bound(eps, layers)
    check(bool(jnp.all(jnp.isfinite(analog))), "non-finite analog logits")
    say("serving", f"{arch_name} d_model={cfg.d_model} heads={cfg.n_heads}/"
        f"{cfg.n_kv_heads}x{cfg.d_head} d_ff={cfg.d_ff} vocab={cfg.vocab} "
        f"layers={layers}/{model.n_layers} requests={prompts}x{prompt_len}"
        f"+{new_tokens} logits_rel_l2={err!r} bound={bound!r} "
        f"peak_bytes_in_use={peak_bytes(jax.devices()[0])} "
        f"setup_s={setup_s:.1f}")
    check(err <= bound, f"analog logits rel_l2 {err} > bound {bound}")


# ------------------------------------------------------------------ four chips
def phase_mesh(jax, jnp, n: int = 65536, cap: int = 2048, cell: int = 512):
    from repro.engine import AnalogEngine
    from repro.launch.mesh import make_mesh
    cfg, imp, key, b = paper_setup(jax, n, cap, cell)
    devs = jax.devices()
    runs = {}
    for name, shape, mesh_devs in (("2x2", (2, 2), devs[:4]),
                                   ("1x1", (1, 1), devs[:1])):
        mesh = make_mesh(shape, ("data", "model"), devices=mesh_devs)
        eng = AnalogEngine(cfg, execution="distributed", mesh=mesh)
        t0 = time.perf_counter()
        _, _, y, res = paper_run(jax, eng, imp, key, b, n)
        jax.block_until_ready((y, res.x))
        runs[name] = (y, res, time.perf_counter() - t0)
    (y4, r4, s4), (y1, r1, s1) = runs["2x2"], runs["1x1"]
    shard_devs = {s.device for s in y4.addressable_shards}
    on0 = lambda v: jax.device_put(v, devs[0])
    mvm_gap, x_gap = rel(on0(y4), y1), rel(on0(r4.x), r1.x)
    peaks = [peak_bytes(d) for d in devs[:4]]
    say("mesh", f"n={n} 2x2_vs_1x1 mvm_rel_l2={mvm_gap!r} "
        f"cg_x_rel_l2={x_gap!r} cg_iters={r4.iterations}/{r1.iterations} "
        f"output_devices={len(shard_devs)} peak_bytes_in_use={peaks} "
        f"setup_s={s4:.1f}/{s1:.1f}")
    check(mvm_gap <= 1e-5, f"2x2 MVM differs from 1x1: {mvm_gap}")
    check(x_gap <= 1e-5, f"2x2 CG solution differs from 1x1: {x_gap}")
    check(r4.iterations == r1.iterations, "CG iteration counts differ")
    check(len(shard_devs) == 4, f"output on {len(shard_devs)} devices, not 4")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"chip_smoke: {src}/repro not found; run from a checkout",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    from repro.launch.compile_cache import enable_compile_cache
    import jax
    import jax.numpy as jnp
    enable_compile_cache()
    dev, limit = phase_device(jax, args.chips)
    if args.chips == 4:
        phase_mesh(jax, jnp)
    else:
        errs = phase_accuracy(jax, jnp)
        phase_paper(jax, jnp)
        phase_resident(jax, jnp, resident_n(limit, 2048))
        phase_serving(jax, jnp, limit=limit,
                      eps=errs["reference", "taox-hfox", True])
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
