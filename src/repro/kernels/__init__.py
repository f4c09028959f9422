"""Pallas TPU kernels for the RRAM crossbar hot spots (validated in
interpret mode on CPU; see ops.py for the public wrappers)."""
from .ops import (
    denoise_stencil,
    denoise_thomas,
    on_cpu,
    rram_ec_matmul,
    rram_ec_tile_mvm,
    rram_encode_matmul,
    solver_cg_update,
    solver_richardson_update,
)

__all__ = [
    "denoise_stencil",
    "denoise_thomas",
    "on_cpu",
    "rram_ec_matmul",
    "rram_ec_tile_mvm",
    "rram_encode_matmul",
    "solver_cg_update",
    "solver_richardson_update",
]
