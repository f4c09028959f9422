"""The system under test: the program's ``AnalogEngine`` and ``solvers.cg``,
built from a configuration file.

The configuration names the engine's execution mode, backend and mesh, the
device constants and the matrix.  The matrix is the benchmark's own
producer (:func:`meliso_reference.banded_block`), handed to the engine as its
``block_fn`` or assembled from it, so an edit of the program's matrices
cannot change what is solved.

A solve is the compiled CG core that ``solvers.cg`` dispatches
(``cg_pipeline`` under ``jit_core``), built once here and called once per
request: ``solvers.cg`` itself jits a fresh core on every call, which would
compile inside the measured window.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

import meliso_reference as ref


def _crossbar_config(conf: dict):
    from repro.core import CrossbarConfig, MCAGeometry, get_device
    dev = conf["device"]
    device = dataclasses.replace(
        get_device(dev["name"]), levels=dev["levels"], sigma0=dev["sigma0"],
        verify_gain=dev["verify_gain"], nl_pot=dev["nl_pot"],
        nl_dep=dev["nl_dep"])
    geom = MCAGeometry(tile_rows=conf["capacity"] // conf["cell_rows"],
                       tile_cols=conf["capacity"] // conf["cell_cols"],
                       cell_rows=conf["cell_rows"], cell_cols=conf["cell_cols"])
    return CrossbarConfig(device=device, geom=geom, k_iters=conf["k_iters"],
                          ec=conf["ec"], ec_mode=conf["ec_mode"],
                          denoise_method=conf["denoise"], lam=conf["lam"],
                          h=conf["h"])


class System:
    """One programmed image and the calls the traffic makes on it."""

    def __init__(self, conf: dict, program_key, devices):
        from repro.engine import AnalogEngine
        self.conf = conf
        spec = ref.Spec.from_config(conf)
        n = spec.n
        cfg = _crossbar_config(conf)
        block_fn = functools.partial(ref.banded_block, spec)
        if conf["execution"] == "distributed":
            from repro.launch.mesh import make_mesh
            rows, cols = conf["mesh"]
            mesh = make_mesh((rows, cols), ("data", "model"),
                             devices=devices[:rows * cols])
            self.engine = AnalogEngine(cfg, execution="distributed",
                                       backend=conf["backend"], mesh=mesh)
            self.matrix = self.engine.program(
                block_fn, program_key, shape=(n, n),
                resident=conf["resident"])
        else:
            self.engine = AnalogEngine(cfg, execution=conf["execution"],
                                       backend=conf["backend"])
            a = jax.jit(functools.partial(ref.dense_matrix, spec))()
            self.matrix = self.engine.program(a, program_key)
            del a
        jax.block_until_ready(self.matrix.at_blocks)
        self._cores = {}

    def solve(self, b, key, *, solver: str, tol: float, maxiter: int):
        """Start one solve; returns device arrays ``(x, iterations, mvms)``."""
        if solver != "cg":
            raise ValueError(f"no {solver!r} solve in this system")
        core = self._cores.get((tol, maxiter))
        if core is None:
            from repro.solvers.base import as_operator, jit_core
            from repro.solvers.krylov import cg_pipeline
            backend = "pallas" if self.conf["backend"] == "pallas" else None
            core = jit_core(as_operator(self.matrix), lambda op: cg_pipeline(
                op, tol=tol, maxiter=maxiter, backend=backend))
            self._cores[(tol, maxiter)] = core
        x, _hist, k, mvms, _rel0 = core(b, jnp.zeros_like(b), key)
        return x, k, mvms

    def mvm(self, x, key):
        """Start one corrected MVM of an (n, cols) panel."""
        return self.engine.mvm(self.matrix, x, key=key)

    def free(self) -> None:
        self._cores.clear()
        self.matrix.release()
        self.matrix = None


def build(conf: dict, program_key, devices) -> System:
    return System(conf, program_key, devices)


def work(conf: dict, cols: int) -> dict:
    """Work of one corrected MVM of ``cols`` columns, fixed by the
    configuration: the fused tier-1 product is two n x n products, so
    ``4 n^2 cols`` flop; a resident image is read once, ``A_tilde`` and
    ``dA`` at their stated dtype, plus the input, its DAC image and the
    output at float32."""
    n = conf["n"]
    image_bytes = 2 * jnp.dtype(conf["dtype"]).itemsize * n * n
    panel_bytes = 3 * 4 * n * cols
    return {"flop": 4 * n * n * cols,
            "bytes": (image_bytes if conf["resident"] else 0) + panel_bytes}
