"""Quickstart: program-once / execute-many corrected MVM in a dozen lines.

    PYTHONPATH=src python examples/quickstart.py

Programs the paper's 66x66 bcsstk02 matrix onto a simulated TaOx-HfOx
multi-MCA crossbar ONCE, then reuses the programmed image for many corrected
MVMs -- the paper's serving model: the write energy is a one-time cost and
every subsequent analog MVM pays only the input-DAC write.  Prints the
Table-1-style comparison against the high-precision EpiRAM device.
"""
import jax
import jax.numpy as jnp

from repro.core import CrossbarConfig, MCAGeometry, get_device, rel_l2
from repro.core.matrices import paper_matrix
from repro.engine import AnalogEngine
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    a = jnp.asarray(paper_matrix("bcsstk02"), jnp.float32)   # kappa = 4325
    key = jax.random.PRNGKey(0)
    xs = [jax.random.normal(jax.random.fold_in(key, i), (66,))
          for i in range(8)]                                 # a serving stream
    geom = MCAGeometry(tile_rows=1, tile_cols=1, cell_rows=66, cell_cols=66)

    print(f"{'device':<12} {'EC':<6} {'rel_l2':>9} {'E_program (J)':>14} "
          f"{'E_per_mvm (J)':>14}")
    for dev_name in ("epiram", "taox-hfox"):
        for ec in (False, True):
            if dev_name == "epiram" and ec:
                continue  # the benchmark device runs raw (paper Table 1)
            cfg = CrossbarConfig(device=get_device(dev_name), geom=geom,
                                 k_iters=5, ec=ec)
            engine = AnalogEngine(cfg)
            A = engine.program(a, jax.random.PRNGKey(1))     # one-time write
            errs = [float(rel_l2(A @ x, a @ x)) for x in xs]  # many executions
            per_call = A.input_write_stats(batch=1)
            print(f"{dev_name:<12} {str(ec):<6} "
                  f"{sum(errs) / len(errs):>9.4f} "
                  f"{float(A.write_stats.energy_j):>14.3e} "
                  f"{float(per_call.energy_j):>14.3e}")

    print("\n-> the noisy-but-fast TaOx-HfOx device + error correction reaches "
          "EpiRAM-class accuracy at ~1000x less write energy (the paper's "
          "headline result) -- and under program-once serving the matrix "
          "write is paid a single time across the whole MVM stream.")


if __name__ == "__main__":
    main()
