"""MELISO+ core: RRAM device models, write-verify, two-tier error correction,
virtualized multi-MCA crossbar simulation, and the distributed MVM engine."""

from .devices import (DEVICES, DeviceModel, drift_factor, effective_sigma,
                      encode, get_device, quantize)
from .write_verify import (
    WriteStats,
    adjustable_mat_write_and_verify,
    adjustable_vec_write_and_verify,
    adjustable_write_and_verify,
)
from .error_correction import (
    build_l_matrix,
    denoise_least_square,
    first_order_correct,
    tridiag_coeffs,
)
from .virtualization import (
    MCAGeometry,
    block_partition,
    generate_vec_chunks,
    reassignment_count,
    zero_padding,
)
from .crossbar import (
    CrossbarConfig,
    block_keys,
    corrected_mvm,
    encode_tiled,
    input_write_cost,
    matrix_write_cost,
    local_block_keys,
    local_dense_mvm,
    local_program_dense,
    produce_blocks,
    producer_is_traceable,
    program_blocks,
    programmed_block_mvm,
    streamed_block_mvm,
    streamed_corrected_mvm,
    streamed_program_blocks,
    write_cost,
)
from .distributed import (
    distributed_corrected_mvm,
    make_distributed_program,
    make_distributed_programmed_mvm,
    make_distributed_streamed_mvm,
    make_distributed_streamed_program,
    mesh_grid_shape,
    pallas_shard_map_supported,
    shard_matrix,
)
from .metrics import rel_l2, rel_linf, relative_error

__all__ = [n for n in dir() if not n.startswith("_")]
