"""Batched serving demo: prefill + greedy decode on any registry arch,
digital or RRAM-analog backend (the paper's technique as a deployment mode).

    PYTHONPATH=src python examples/serve_lm.py
    PYTHONPATH=src python examples/serve_lm.py --rram --device taox-hfox

With --rram the weights are programmed onto simulated crossbars once
(write energy/latency reported -- the analog deployment's one-time cost) and
every matmul runs the fused two-tier-EC analog path.
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_arch, model_module
from repro.configs.base import RRAMBackendConfig
from repro.models import params as PM
from repro.models.common import Runtime
from repro.train.serve import Server
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--rram", action="store_true")
    ap.add_argument("--device", default="taox-hfox")
    ap.add_argument("--no-ec", action="store_true")
    args = ap.parse_args()

    arch = get_arch(args.arch)
    cfg = arch.reduced()
    mod = model_module(cfg)
    params = PM.materialize(mod.init_specs(cfg), jax.random.PRNGKey(0))

    rt = Runtime()
    if args.rram:
        rt = Runtime(rram=RRAMBackendConfig(
            enabled=True, device=args.device, ec=not args.no_ec,
            cell_rows=32, cell_cols=32, k_iters=5),
            key=jax.random.PRNGKey(9))

    srv = Server(mod, cfg, params, rt=rt,
                 max_len=args.prompt_len + args.tokens + 8)
    if srv.write_stats is not None:
        print(f"analog programming: E={float(srv.write_stats.energy_j):.3e} J, "
              f"L={float(srv.write_stats.latency_s):.3e} s "
              f"(one-time, device={args.device})")

    prompts = jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0, cfg.vocab)
    batch = {"tokens": prompts}
    if cfg.family == "whisper":
        batch["frames"] = jax.random.normal(
            jax.random.PRNGKey(2), (args.batch, args.prompt_len, cfg.d_model))
    if cfg.family == "llama_vision":
        batch["patches"] = jax.random.normal(
            jax.random.PRNGKey(2), (args.batch, cfg.n_patches, cfg.d_model))

    t0 = time.perf_counter()
    out = srv.generate(batch, args.tokens)
    dt = time.perf_counter() - t0
    total = args.batch * args.tokens
    print(f"arch={args.arch} backend={'rram' if args.rram else 'digital'} "
          f"batch={args.batch}")
    print(f"generated {total} tokens in {dt:.2f}s "
          f"({total / dt:.1f} tok/s incl. prefill+compile)")
    print("first sequence:", out[0][:16].tolist())


if __name__ == "__main__":
    main()
