"""Multi-MCA crossbar simulation engine (reference, pure-jnp).

Combines the device models, write-verify encoding, virtualization and the
two-tier error correction into the paper's ``correctedMatVecMul`` /
``distributedMatVecMul`` dataflow, with analytic write-energy / write-latency
accounting that follows the paper's conventions:

  * energy  = every programmed cell costs ``e_write`` per pass (zero padding is
              programmed too, faithfully -- ``skip_zero_pad_writes`` turns on the
              beyond-paper optimization of eliding all-zero chunk writes);
  * latency = rows of one MCA are programmed sequentially, MCAs operate in
              parallel, reassignments (virtualization) serialize; the paper
              reports the *mean across MCAs* (Figs. 4-5), which for a uniform
              workload equals the per-MCA value;
  * passes  = k_iters + 1 write-verify passes (the paper sweeps fixed k);
  * EC      = one extra array write (the replicated X^T matrix, paper sec. 2)
              per assignment plus the input-vector write.

The Pallas kernel in :mod:`repro.kernels.rram_mvm` implements the same
encode+multiply semantics per (cell_rows x cell_cols) VMEM tile; this module is
its oracle at system level.

Each stage names its operations with a ``jax.named_scope`` inside the stage
function, so every path that calls it carries the name into the compiled
program's op metadata and a profiler trace: ``meliso.produce`` (the block
producer), ``meliso.encode`` (programming), ``meliso.dac`` (input DAC),
``meliso.tier1`` (EC products and their accumulation); ``meliso.tier2`` is
set in :func:`repro.core.error_correction.denoise_least_square` and
``meliso.psum`` in :mod:`repro.core.distributed`.  A scope is metadata: it
adds no jaxpr equation and no work.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from .devices import DeviceModel, effective_sigma, effective_sigma_py, quantize
from .error_correction import denoise_least_square
from .virtualization import MCAGeometry, zero_padding
from .write_verify import WriteStats

__all__ = [
    "PRECISION",
    "matmul",
    "CrossbarConfig",
    "encode_tiled",
    "write_cost",
    "matrix_write_cost",
    "input_write_cost",
    "tile_write_cost",
    "block_keys",
    "capacity_elements",
    "local_block_keys",
    "program_blocks",
    "programmed_block_mvm",
    "programmed_block_rmvm",
    "local_program_dense",
    "local_dense_mvm",
    "local_dense_rmvm",
    "group_program_blocks",
    "grouped_block_mvm",
    "grouped_block_rmvm",
    "grouped_streamed_program_blocks",
    "grouped_streamed_block_mvm",
    "grouped_streamed_block_rmvm",
    "produce_blocks",
    "producer_is_traceable",
    "streamed_program_blocks",
    "streamed_block_mvm",
    "streamed_block_rmvm",
    "corrected_mvm",
    "streamed_corrected_mvm",
]


#: Precision of every float32 product in the simulated dataflow.  A TPU's
#: default float32 matmul is a single bf16 pass (~3 significant digits),
#: coarser than the tier-1 correction ``dA x_tilde`` it would carry; HIGHEST
#: keeps the products at float32, as on the CPU backend.
PRECISION = jax.lax.Precision.HIGHEST


def matmul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """``a @ b`` at :data:`PRECISION`."""
    return jnp.matmul(a, b, precision=PRECISION)


@dataclasses.dataclass(frozen=True)
class CrossbarConfig:
    """Everything needed to run one corrected MVM on a multi-MCA system."""

    device: DeviceModel
    geom: MCAGeometry = MCAGeometry()
    k_iters: int = 5                    # fixed write-verify iterations (paper Fig. 2-3)
    ec: bool = True                     # two-tier error correction on/off
    ec_mode: str = "fused"              # "faithful" (3 products) | "fused" (2)
    denoise_method: str = "neumann"     # "dense" | "thomas" | "neumann"
    lam: float = 1e-12
    h: float = -1.0
    encode_inputs: bool = True          # inputs (x) also pass through the DAC/encode
    skip_zero_pad_writes: bool = False  # beyond-paper: don't program all-zero chunks


# --------------------------------------------------------------------------- #
# Encoding
# --------------------------------------------------------------------------- #

def encode_tiled(
    a: jnp.ndarray,
    key: jax.Array,
    cfg: CrossbarConfig,
) -> jnp.ndarray:
    """Encode a (padded) matrix with *per-MCA-tile* quantization scales.

    ``a`` is (M, N) with M, N multiples of the cell size; each (r x c) tile gets
    its own conductance range (per-array DAC scaling), quantization to the
    device's levels and residual programming noise after ``k_iters`` verify
    passes.
    """
    dev, geom = cfg.device, cfg.geom
    r_, c_ = geom.cell_rows, geom.cell_cols
    m, n = a.shape
    assert m % r_ == 0 and n % c_ == 0, (a.shape, (r_, c_))
    with jax.named_scope("meliso.encode"):
        # Per-tile quantization without physical transposes: the
        # (mt, r, nt, c) view is a pure reshape, the per-tile scale reduces
        # axes (1, 3) in place (two whole-matrix transposes removed --
        # EXPERIMENTS.md Perf M1).
        tiles = a.reshape(m // r_, r_, n // c_, c_)
        q = quantize(tiles, dev.levels, axis=(1, 3))
        sigma = effective_sigma(dev, cfg.k_iters).astype(a.dtype)
        eta = jax.random.normal(key, tiles.shape, dtype=a.dtype)
        enc = q * (1.0 + sigma * eta)
        return enc.reshape(m, n)


def _encode_vec(x: jnp.ndarray, key: jax.Array, cfg: CrossbarConfig) -> jnp.ndarray:
    """Input-DAC encode of (n,) or (n, batch) inputs.  Each column is its own
    DAC write with its own range, so a batched MVM quantizes every column
    exactly as it would be quantized alone."""
    dev = cfg.device
    with jax.named_scope("meliso.dac"):
        q = quantize(x, dev.levels, axis=0)
        sigma = effective_sigma(dev, cfg.k_iters).astype(x.dtype)
        eta = jax.random.normal(key, x.shape, dtype=x.dtype)
        return q * (1.0 + sigma * eta)


# --------------------------------------------------------------------------- #
# Analytic write cost (paper Figs. 2-5 accounting)
# --------------------------------------------------------------------------- #

def write_cost(
    m: int,
    n: int,
    cfg: CrossbarConfig,
    batch: int = 1,
    *,
    include_matrix: bool = True,
    include_inputs: bool = True,
    transpose: bool = False,
) -> WriteStats:
    """Analytic write energy/latency for one corrected MVM of an (m, n) problem.

    The total splits into a *matrix* part (programming the conductance image --
    paid once under the program-once API) and an *input* part (the per-call x
    vector write plus the EC X^T replica, scaling with ``batch``).  The
    ``include_*`` switches select the parts; :func:`matrix_write_cost` and
    :func:`input_write_cost` are the named halves.

    ``transpose=True`` bills the input part of a *transposed* execution
    (``A.T @ y``, DESIGN.md section 5): the DAC vector then has ``m`` entries
    (padded to the capacity row footprint) and the EC replica is the
    row-dimension ``Y^T`` array (r x r per MCA assignment instead of c x c).
    The matrix part is unchanged -- the transposed execution reuses the one
    programmed image, paying zero extra matrix writes.
    """
    dev, geom = cfg.device, cfg.geom
    cap_m, cap_n = geom.capacity
    mb = -(-m // cap_m)
    nb = -(-n // cap_n)
    reass = mb * nb
    passes = float(cfg.k_iters + 1)

    energy = 0.0
    latency = 0.0
    if include_matrix:
        if cfg.skip_zero_pad_writes:
            # Only the cells covering the true (m, n) footprint are programmed.
            cells_a = float(m) * float(n)
            rows_a_per_mca = reass * min(geom.cell_rows, max(1, m))
        else:
            cells_a = float(mb * cap_m) * float(nb * cap_n)
            rows_a_per_mca = reass * geom.cell_rows
        energy += cells_a * dev.e_write
        latency += rows_a_per_mca * dev.t_write

    # Input-side footprint: forward executions write the (padded) n-length x
    # vector and the c x c EC X^T replica; transposed executions write the
    # m-length y vector and the r x r EC Y^T replica against the same image.
    c_ = geom.cell_rows if transpose else geom.cell_cols
    n_pad = mb * cap_m if transpose else nb * cap_n
    if include_inputs:
        if cfg.encode_inputs:
            energy += float(n_pad) * batch * dev.e_write        # x vector write
            latency += 1.0 * batch * dev.t_write
        if cfg.ec:
            # The replicated X^T array (c x c per MCA assignment, paper sec. 2).
            energy += float(reass * geom.n_mcas) * (c_ * c_) * batch * dev.e_write
            latency += reass * c_ * batch * dev.t_write
    # Pure-Python math throughout: this function is called inside shard_map
    # traces, where any jnp op would produce (un-float-able) tracers.
    return WriteStats(
        energy_j=jnp.float32(energy * passes),
        latency_s=jnp.float32(latency * passes),
        iterations=jnp.int32(cfg.k_iters),
        final_delta=jnp.float32(effective_sigma_py(dev, cfg.k_iters)),
    )


def matrix_write_cost(m: int, n: int, cfg: CrossbarConfig) -> WriteStats:
    """One-time programming cost of the (m, n) conductance image."""
    return write_cost(m, n, cfg, include_inputs=False)


def tile_write_cost(cfg: CrossbarConfig) -> WriteStats:
    """Programming cost of ONE capacity block (cap_m x cap_n).

    The unit the refresh controller budgets in
    (:mod:`repro.reliability.refresh`): re-verifying ``k`` worst tiles costs
    ``k`` of these against the full :func:`matrix_write_cost` of a complete
    reprogram -- the amortization that makes tile-selective refresh win."""
    cap_m, cap_n = cfg.geom.capacity
    return matrix_write_cost(cap_m, cap_n, cfg)


def input_write_cost(m: int, n: int, cfg: CrossbarConfig,
                     batch: int = 1, *, transpose: bool = False) -> WriteStats:
    """Per-execution cost: x-vector DAC write + EC X^T replica, per column.

    ``transpose=True`` bills a transposed execution (m-length y vector + the
    row-dimension EC replica; see :func:`write_cost`)."""
    return write_cost(m, n, cfg, batch=batch, include_matrix=False,
                      transpose=transpose)


# --------------------------------------------------------------------------- #
# Program stage / execute stage (the program-once dataflow)
# --------------------------------------------------------------------------- #
#
# The paper's dataflow is program-once / execute-many: the conductance image
# A_tilde is written to the MCAs one time, then reused across MVMs.  The
# functions below factor the old monolithic ``corrected_mvm`` into those two
# stages; :class:`repro.engine.AnalogEngine` is the public handle-based API on
# top, and the legacy entry points at the bottom of this file are thin
# compositions kept for backwards compatibility.
#
# Key discipline (shared by both stages so that program+execute reproduces the
# fused legacy path draw-for-draw): the base key splits into one key per
# capacity block, and each block key splits into (k_a, k_x) -- programming
# consumes k_a, execution consumes k_x.


def block_keys(key: jax.Array, mb: int, nb: int) -> jax.Array:
    """Per-capacity-block PRNG keys, shaped (mb, nb, ...)."""
    keys = jax.random.split(key, mb * nb)
    return keys.reshape((mb, nb) + keys.shape[1:])   # typed or raw key format


def capacity_elements(cfg: CrossbarConfig) -> int:
    """Elements of one capacity block -- the unit every streamed/distributed
    memory budget is expressed in (the AvalBound pass of the invariant gate
    asserts multiples of this; see DESIGN.md section 10)."""
    cap_m, cap_n = cfg.geom.capacity
    return cap_m * cap_n


def local_block_keys(key: jax.Array, mb: int, nb: int, i0, j0,
                     grid: Optional[Tuple[int, int]]) -> jax.Array:
    """The (mb, nb) slab of the GLOBAL ``block_keys(key, *grid)`` schedule
    whose origin sits at block coordinates ``(i0, j0)``.

    The per-block key is a function of the global block index only -- never of
    how the grid is carved across devices -- so the encoded image (and every
    DAC draw) of block (I, J) is identical whether the grid runs on one device
    or is mesh-sharded.  ``i0``/``j0`` may be traced scalars (mesh coordinates
    inside shard_map).  ``grid=None`` means the local grid IS the global grid.
    """
    if grid is None:
        return block_keys(key, mb, nb)
    keys = block_keys(key, *grid)
    start = (i0, j0) + (0,) * (keys.ndim - 2)
    return jax.lax.dynamic_slice(keys, start, (mb, nb) + keys.shape[2:])


def assemble_blocks(blocks: jnp.ndarray, m: int, n: int) -> jnp.ndarray:
    """Inverse of :func:`repro.core.virtualization.block_partition`:
    (mb, nb, cap_m, cap_n) capacity tiles -> dense (m, n), padding sliced."""
    mb, nb, cm, cn = blocks.shape
    return blocks.transpose(0, 2, 1, 3).reshape(mb * cm, nb * cn)[:m, :n]


def program_blocks(
    a: jnp.ndarray,
    key: jax.Array,
    cfg: CrossbarConfig,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Program stage: encode A onto the (virtual) MCAs, once.

    Returns ``(at_blocks, da_blocks)``, both (mb, nb, cap_m, cap_n):
    the per-block conductance images ``A_tilde`` and the tier-1 correction
    operands ``dA = A - A_tilde`` (paper Eq. 7, with the first-order product
    rewritten as  p = A_tilde x + dA x_tilde).  Block rows are encoded one
    after another, so programming needs O(one block row) of memory beyond
    ``a`` and the two outputs.
    """
    cap_m, cap_n = cfg.geom.capacity
    a_pad = zero_padding(a, cfg.geom)
    mp, np_ = a_pad.shape
    mb, nb = mp // cap_m, np_ // cap_n
    keys = block_keys(key, mb, nb)

    def enc_one(a_blk, k):
        k_a, _ = jax.random.split(k)
        return encode_tiled(a_blk, k_a, cfg)

    def enc_row(ops):
        band, row_keys = ops
        with jax.named_scope("meliso.encode"):
            row_blocks = band.reshape(cap_m, nb, cap_n).transpose(1, 0, 2)
            at_row = jax.vmap(enc_one)(row_blocks, row_keys)
            return at_row, row_blocks - at_row

    return jax.lax.map(enc_row, (a_pad.reshape(mb, cap_m, np_), keys))


def programmed_block_mvm(
    at_blocks: jnp.ndarray,
    da_blocks: jnp.ndarray,
    xb: jnp.ndarray,
    key: jax.Array,
    cfg: CrossbarConfig,
    *,
    m: int,
    n: int,
    tier2: bool = True,
    use_kernel: bool = False,
) -> jnp.ndarray:
    """Execute stage: corrected MVM against an already-programmed image.

    ``xb`` is (n, batch).  Performs zero matrix-encode work: only the input
    vector passes through the DAC (x -> x_tilde, per block, consuming the k_x
    half of the block key), the tier-1 product is assembled from the stored
    operands as  p = A_tilde x + dA x_tilde,  column-block partials are summed
    and tier-2 denoising runs on the assembled output (``tier2=False`` defers
    it, e.g. until after a cross-device psum).  ``use_kernel=True`` dispatches
    the per-block tier-1 product to the fused Pallas
    :func:`repro.kernels.ops.rram_ec_tile_mvm` tile step (requires
    ``cfg.ec``).  Returns (m, batch).
    """
    mb, nb, cap_m, cap_n = at_blocks.shape
    batch = xb.shape[1]
    x_pad = jnp.pad(xb, ((0, nb * cap_n - n), (0, 0)))
    x_chunks = x_pad.reshape(nb, cap_n, batch)
    keys = block_keys(key, mb, nb)

    if cfg.ec and cfg.ec_mode not in ("fused", "faithful"):
        raise ValueError(f"unknown first-order EC mode {cfg.ec_mode!r}")

    def per_row(at_row, da_row, row_keys):
        def per_col(at_blk, da_blk, x_blk, k):
            _, k_x = jax.random.split(k)
            x_t = _encode_vec(x_blk, k_x, cfg) if cfg.encode_inputs else x_blk
            with jax.named_scope("meliso.tier1"):
                if not cfg.ec:
                    return matmul(at_blk, x_t)
                if use_kernel:
                    from repro.kernels import ops as kops
                    return kops.rram_ec_tile_mvm(x_blk, x_t, at_blk, da_blk)
                if cfg.ec_mode == "faithful":
                    # The paper's three analog products, A = A_tilde + dA.
                    return (matmul(at_blk, x_blk)
                            + matmul(at_blk + da_blk, x_t)
                            - matmul(at_blk, x_t))
                return matmul(at_blk, x_blk) + matmul(da_blk, x_t)  # fused
        partials = jax.vmap(per_col)(at_row, da_row, x_chunks, row_keys)
        with jax.named_scope("meliso.tier1"):
            return jnp.sum(partials, axis=0)             # sum over column blocks

    y_blocks = jax.vmap(per_row)(at_blocks, da_blocks, keys)   # (mb, cap_m, batch)
    p = y_blocks.reshape(mb * cap_m, batch)[:m]
    if cfg.ec and tier2:
        p = denoise_least_square(p, lam=cfg.lam, h=cfg.h, method=cfg.denoise_method)
    return p


def programmed_block_rmvm(
    at_blocks: jnp.ndarray,
    da_blocks: jnp.ndarray,
    yb: jnp.ndarray,
    key: jax.Array,
    cfg: CrossbarConfig,
    *,
    m: int,
    n: int,
    tier2: bool = True,
    use_kernel: bool = False,
) -> jnp.ndarray:
    """Transposed execute stage: corrected ``A.T @ y`` against the programmed
    image -- zero re-encode of the conductance image.

    The exact mirror of :func:`programmed_block_mvm` run backwards through the
    crossbar: ``yb`` is (m, batch), the input vector is the ROW-dimension
    chunking of y (each row-block chunk passes through the DAC, consuming the
    SAME k_x key half of block (i, j) as a forward execution would), the
    tier-1 product is assembled from the stored operands as
    ``p = A_tilde^T y + dA^T y_tilde``, ROW-block partials are summed (rows
    are the contraction axis of A^T) and tier-2 denoising runs over the
    assembled (n, batch) column output.  ``use_kernel=True`` dispatches the
    per-block product to the fused Pallas
    :func:`repro.kernels.ops.rram_ec_tile_rmvm` tile step.  Returns (n, batch).
    """
    mb, nb, cap_m, cap_n = at_blocks.shape
    batch = yb.shape[1]
    y_pad = jnp.pad(yb, ((0, mb * cap_m - m), (0, 0)))
    y_chunks = y_pad.reshape(mb, cap_m, batch)
    keys = block_keys(key, mb, nb)

    if cfg.ec and cfg.ec_mode not in ("fused", "faithful"):
        raise ValueError(f"unknown first-order EC mode {cfg.ec_mode!r}")

    def per_col(at_col, da_col, col_keys):
        def per_row(at_blk, da_blk, y_blk, k):
            _, k_x = jax.random.split(k)
            y_t = _encode_vec(y_blk, k_x, cfg) if cfg.encode_inputs else y_blk
            with jax.named_scope("meliso.tier1"):
                if not cfg.ec:
                    return matmul(at_blk.T, y_t)
                if use_kernel:
                    from repro.kernels import ops as kops
                    return kops.rram_ec_tile_rmvm(y_blk, y_t, at_blk, da_blk)
                if cfg.ec_mode == "faithful":
                    # The paper's three analog products, transposed.
                    return (matmul(at_blk.T, y_blk)
                            + matmul((at_blk + da_blk).T, y_t)
                            - matmul(at_blk.T, y_t))
                return matmul(at_blk.T, y_blk) + matmul(da_blk.T, y_t)
        partials = jax.vmap(per_row)(at_col, da_col, y_chunks, col_keys)
        with jax.named_scope("meliso.tier1"):
            return jnp.sum(partials, axis=0)                # sum over row blocks

    z_blocks = jax.vmap(per_col)(at_blocks.swapaxes(0, 1),
                                 da_blocks.swapaxes(0, 1),
                                 keys.swapaxes(0, 1))        # (nb, cap_n, batch)
    p = z_blocks.reshape(nb * cap_n, batch)[:n]
    if cfg.ec and tier2:
        p = denoise_least_square(p, lam=cfg.lam, h=cfg.h, method=cfg.denoise_method)
    return p


def local_program_dense(a: jnp.ndarray, key: jax.Array, cfg: CrossbarConfig
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One device's program stage over a resident dense operand.

    The per-device half of the distributed dense pipeline, shared with the
    local path: :func:`program_blocks` + reassembly to the dense per-device
    layout (the placed conductance image / tier-1 operand).
    """
    m, n = a.shape
    at_b, da_b = program_blocks(a, key, cfg)
    return assemble_blocks(at_b, m, n), assemble_blocks(da_b, m, n)


def local_dense_mvm(
    at: jnp.ndarray,
    da: jnp.ndarray,
    xb: jnp.ndarray,
    key: jax.Array,
    cfg: CrossbarConfig,
    *,
    tier2: bool = True,
    use_kernel: bool = False,
) -> jnp.ndarray:
    """One device's execute stage over resident dense (m, n) operands.

    Partitions to capacity blocks and runs the shared
    :func:`programmed_block_mvm` pipeline -- the SAME implementation the
    local execution mode uses, so the distributed path has no private copy
    of the tier-1 dataflow.  ``tier2=False`` defers denoising until after
    the cross-device psum (the caller's "on-node" tier-2).
    """
    from .virtualization import block_partition
    m, n = at.shape
    return programmed_block_mvm(
        block_partition(at, cfg.geom), block_partition(da, cfg.geom),
        xb, key, cfg, m=m, n=n, tier2=tier2, use_kernel=use_kernel)


def local_dense_rmvm(
    at: jnp.ndarray,
    da: jnp.ndarray,
    yb: jnp.ndarray,
    key: jax.Array,
    cfg: CrossbarConfig,
    *,
    tier2: bool = True,
    use_kernel: bool = False,
) -> jnp.ndarray:
    """One device's transposed execute stage over resident dense operands.

    Partitions to capacity blocks and runs the shared
    :func:`programmed_block_rmvm` pipeline -- the same implementation the
    local execution mode uses, so the distributed transposed path has no
    private copy of the tier-1 dataflow.  ``tier2=False`` defers denoising
    until after the cross-device psum over the ROW axes."""
    from .virtualization import block_partition
    m, n = at.shape
    return programmed_block_rmvm(
        block_partition(at, cfg.geom), block_partition(da, cfg.geom),
        yb, key, cfg, m=m, n=n, tier2=tier2, use_kernel=use_kernel)


# --------------------------------------------------------------------------- #
# Grouped (multi-image) stages: one pipeline over a stack of programmed images
# --------------------------------------------------------------------------- #
#
# A *group* stacks the per-tile images of several same-geometry matrices along
# a leading image axis ``g`` and runs the whole stack as ONE pipeline -- the
# whole-model dispatch primitive behind :class:`repro.engine.AnalogMatrixGroup`
# (an analog transformer block, or all experts of an MoE layer, executes as a
# single device dispatch instead of one per member).  Every grouped stage is a
# ``vmap``/``lax.map`` of the corresponding solo stage with PER-MEMBER keys, so
# member ``g`` of a grouped program/execute consumes exactly the
# ``block_keys(keys[g], mb, nb)`` schedule its solo counterpart would: the
# stacked image is bit-identical, member for member, to solo programming, and
# every grouped DAC draw matches the solo draw under the same member key.

def group_program_blocks(
    a_stack: jnp.ndarray,
    keys: jax.Array,
    cfg: CrossbarConfig,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Program a stack of same-shape matrices in one pipeline.

    ``a_stack`` is (g, m, n); ``keys`` holds one base key per member.  Returns
    ``(at_blocks, da_blocks)``, both (g, mb, nb, cap_m, cap_n).  Member ``g``
    is :func:`program_blocks`\\ ``(a_stack[g], keys[g], cfg)`` exactly (same
    per-block k_a halves, same draws) -- grouping changes the dispatch count,
    never the image.
    """
    return jax.vmap(lambda a, k: program_blocks(a, k, cfg))(a_stack, keys)


def grouped_block_mvm(
    at_blocks: jnp.ndarray,
    da_blocks: jnp.ndarray,
    xb: jnp.ndarray,
    keys: jax.Array,
    cfg: CrossbarConfig,
    *,
    m: int,
    n: int,
    tier2: bool = True,
    use_kernel: bool = False,
) -> jnp.ndarray:
    """Corrected MVM of every group member in one pipeline.

    ``at_blocks``/``da_blocks`` are (g, mb, nb, cap_m, cap_n) stacked images,
    ``xb`` is (g, n, batch) -- one input panel per member -- and ``keys`` one
    execute key per member.  Returns (g, m, batch).  Member ``g`` reproduces
    :func:`programmed_block_mvm` under ``keys[g]`` (the identical per-block
    k_x halves), including tier-2 denoise per member.  ``use_kernel=True``
    runs the fused Pallas tile step under a member ``lax.map`` (the kernel
    sees one member at a time -- the extra image axis never reaches the
    pallas grid).
    """
    run = partial(programmed_block_mvm, cfg=cfg, m=m, n=n, tier2=tier2,
                  use_kernel=use_kernel)
    if use_kernel:
        return jax.lax.map(lambda ops: run(*ops),
                           (at_blocks, da_blocks, xb, keys))
    return jax.vmap(lambda at, da, x, k: run(at, da, x, k))(
        at_blocks, da_blocks, xb, keys)


def grouped_block_rmvm(
    at_blocks: jnp.ndarray,
    da_blocks: jnp.ndarray,
    yb: jnp.ndarray,
    keys: jax.Array,
    cfg: CrossbarConfig,
    *,
    m: int,
    n: int,
    tier2: bool = True,
    use_kernel: bool = False,
) -> jnp.ndarray:
    """Transposed grouped execute: ``A_g.T @ y_g`` for every member at once.

    The exact mirror of :func:`grouped_block_mvm` over
    :func:`programmed_block_rmvm`: ``yb`` is (g, m, batch), the result
    (g, n, batch), and member ``g`` consumes the same per-block k_x halves a
    solo transposed execute under ``keys[g]`` would.
    """
    run = partial(programmed_block_rmvm, cfg=cfg, m=m, n=n, tier2=tier2,
                  use_kernel=use_kernel)
    if use_kernel:
        return jax.lax.map(lambda ops: run(*ops),
                           (at_blocks, da_blocks, yb, keys))
    return jax.vmap(lambda at, da, y, k: run(at, da, y, k))(
        at_blocks, da_blocks, yb, keys)


def _switched_producer(block_fns: Tuple[Callable, ...], g: jax.Array):
    """Member ``g``'s producer as one traceable fn: a ``lax.switch`` over the
    member list (``g`` may be a scan-carried tracer -- only the selected
    branch executes at runtime)."""
    branches = tuple((lambda i, j, f=f: f(i, j)) for f in block_fns)
    return lambda i, j: jax.lax.switch(g, branches, i, j)


def grouped_streamed_program_blocks(
    block_fns: Tuple[Callable, ...],
    keys: jax.Array,
    cfg: CrossbarConfig,
    mb: int,
    nb: int,
) -> jnp.ndarray:
    """Scan-program a group of streamed producers in one pipeline.

    One ``lax.map`` over members, each running the scan-fused
    :func:`streamed_program_blocks` sweep with its own producer (selected by
    ``lax.switch`` on the member index) and its own key schedule -- member
    ``g``'s image is bit-identical to its solo streamed program.  Returns
    (g, mb, nb, cap_m, cap_n).
    """
    def one(ops):
        g, k = ops
        return streamed_program_blocks(
            _switched_producer(block_fns, g), k, cfg, mb, nb)

    return jax.lax.map(one, (jnp.arange(len(block_fns)), keys))


def grouped_streamed_block_mvm(
    block_fns: Tuple[Callable, ...],
    at_blocks: jnp.ndarray,
    xb: jnp.ndarray,
    keys: jax.Array,
    cfg: CrossbarConfig,
    *,
    m: int,
    n: int,
    use_kernel: bool = False,
    tier2: bool = True,
) -> jnp.ndarray:
    """Grouped streamed execute: every member's scan-fused MVM in one
    pipeline (dA re-derived per block from each member's own producer).

    ``at_blocks`` is the (g, mb, nb, cap_m, cap_n) stacked resident image,
    ``xb`` (g, n, batch).  Member ``g`` reproduces :func:`streamed_block_mvm`
    under ``keys[g]`` exactly.  Returns (g, m, batch).
    """
    def one(ops):
        g, at, x, k = ops
        return streamed_block_mvm(
            _switched_producer(block_fns, g), at, x, k, cfg, m=m, n=n,
            use_kernel=use_kernel, tier2=tier2)

    return jax.lax.map(one, (jnp.arange(len(block_fns)), at_blocks, xb, keys))


def grouped_streamed_block_rmvm(
    block_fns: Tuple[Callable, ...],
    at_blocks: jnp.ndarray,
    yb: jnp.ndarray,
    keys: jax.Array,
    cfg: CrossbarConfig,
    *,
    m: int,
    n: int,
    use_kernel: bool = False,
    tier2: bool = True,
) -> jnp.ndarray:
    """Grouped streamed TRANSPOSED execute: the :func:`streamed_block_rmvm`
    mirror of :func:`grouped_streamed_block_mvm` (``yb`` (g, m, batch) ->
    (g, n, batch), same per-block k_x halves per member as forward)."""
    def one(ops):
        g, at, y, k = ops
        return streamed_block_rmvm(
            _switched_producer(block_fns, g), at, y, k, cfg, m=m, n=n,
            use_kernel=use_kernel, tier2=tier2)

    return jax.lax.map(one, (jnp.arange(len(block_fns)), at_blocks, yb, keys))


# --------------------------------------------------------------------------- #
# Scan-fused streamed stages (single-dispatch pipelines over a block producer)
# --------------------------------------------------------------------------- #
#
# The streamed execution mode consumes a *traceable* block producer
# ``block_fn(i, j) -> (cap_m, cap_n) block``: a pure jax function of the two
# block-index scalars (which may be tracers).  That protocol lets the whole
# mb x nb block sweep trace into ONE ``lax.scan`` program -- one device
# dispatch per program / per MVM -- instead of the O(mb * nb) host->device
# launches of a Python double loop.  Opaque Python producers (``int(i)``
# indexing, file reads, ...) cannot trace; :class:`repro.engine.AnalogEngine`
# keeps a compatibility host loop for those.
#
# All three functions below are pure jax (jit/vmap/scan-safe); the engine owns
# the jit caching (``block_fn`` is a static argument there).


def _produce(block_fn: Callable[[jax.Array, jax.Array], jnp.ndarray],
             i, j) -> jnp.ndarray:
    """``block_fn(i, j)``: every traced call of a producer goes through here,
    so its operations are named ``meliso.produce`` in traces."""
    with jax.named_scope("meliso.produce"):
        return block_fn(i, j)


def producer_is_traceable(block_fn, cap_m: int, cap_n: int) -> bool:
    """True when ``block_fn(i, j)`` abstractly traces to a (cap_m, cap_n)
    block from two int32 scalars (the traceable-producer protocol).

    An explicit ``block_fn.traceable`` attribute short-circuits the probe
    (``False`` forces the host loop, e.g. for producers whose trace would be
    valid but unwanted).  The probe itself is one ``jax.eval_shape`` -- no
    FLOPs, no device dispatch.
    """
    forced = getattr(block_fn, "traceable", None)
    if forced is not None:
        return bool(forced)
    idx = jax.ShapeDtypeStruct((), jnp.int32)
    try:
        out = jax.eval_shape(block_fn, idx, idx)
    except Exception:
        return False
    return getattr(out, "shape", None) == (cap_m, cap_n)


def produce_blocks(block_fn: Callable[[jax.Array, jax.Array], jnp.ndarray],
                   mb: int, nb: int) -> jnp.ndarray:
    """Materialize all (mb, nb) producer blocks with one two-level scan.

    Returns (mb, nb, cap_m, cap_n).  One traced call of ``block_fn`` instead
    of mb * nb host invocations -- the single-dispatch path behind the
    streamed ``AnalogMatrix.da`` / ``dense()`` views.
    """
    def row_step(_, i):
        def col_step(_, j):
            return None, _produce(block_fn, i, j)
        _, row = jax.lax.scan(col_step, None, jnp.arange(nb))
        return None, row

    _, blocks = jax.lax.scan(row_step, None, jnp.arange(mb))
    return blocks


def streamed_program_blocks(
    block_fn: Callable[[jax.Array, jax.Array], jnp.ndarray],
    key: jax.Array,
    cfg: CrossbarConfig,
    mb: int,
    nb: int,
    *,
    block_offset=(0, 0),
    grid: Optional[Tuple[int, int]] = None,
) -> jnp.ndarray:
    """Scan-fused program stage over a traceable producer.

    One ``lax.scan`` over the block-index grid encodes every capacity block
    (same per-block keys and draws as :func:`program_blocks`: the k_a half of
    ``block_keys(key, mb, nb)``), so programming a streamed handle is a single
    device dispatch.  Returns ``at_blocks`` (mb, nb, cap_m, cap_n); the tier-1
    operand dA is intentionally NOT returned -- streamed handles re-derive it
    from the producer at execute time so the source matrix is never resident
    twice.

    ``grid=(MB, NB)`` / ``block_offset=(i0, j0)`` program only the local
    (mb, nb) window of a larger global block grid: the producer is called with
    GLOBAL block indices and the per-block keys come from the global
    :func:`block_keys` schedule (see :func:`local_block_keys`), so a
    mesh-sharded program writes exactly the same conductance image, block for
    block, as the single-device sweep.  The offsets may be traced scalars
    (``jax.lax.axis_index`` inside shard_map).
    """
    i0, j0 = block_offset
    keys = local_block_keys(key, mb, nb, i0, j0, grid)

    def row_step(_, row_xs):
        row_keys, i = row_xs

        def col_step(_, col_xs):
            k, j = col_xs
            k_a, _k_x = jax.random.split(k)
            return None, encode_tiled(_produce(block_fn, i, j), k_a, cfg)

        _, at_row = jax.lax.scan(col_step, None, (row_keys, j0 + jnp.arange(nb)))
        return None, at_row

    _, at_blocks = jax.lax.scan(row_step, None, (keys, i0 + jnp.arange(mb)))
    return at_blocks


def streamed_block_mvm(
    block_fn: Callable[[jax.Array, jax.Array], jnp.ndarray],
    at_blocks: Optional[jnp.ndarray],
    xb: jnp.ndarray,
    key: jax.Array,
    cfg: CrossbarConfig,
    *,
    m: int,
    n: int,
    use_kernel: bool = False,
    tier2: bool = True,
    block_offset=(0, 0),
    grid: Optional[Tuple[int, int]] = None,
) -> jnp.ndarray:
    """Scan-fused execute stage over a streamed block producer.

    One ``lax.scan`` over row blocks (inner scan over column blocks with
    in-place fp32 row accumulation) replaces the per-block host loop: the
    input-DAC encode, the per-block ``dA = block_fn(i, j) - at_blocks[i, j]``
    re-derivation, the tier-1 EC product (``use_kernel=True`` fuses it into
    the Pallas :func:`repro.kernels.rram_ec_matmul` tile step) and the partial
    reduction all live inside one traced program -- one device dispatch per
    MVM.  Key/draw schedule matches :func:`programmed_block_mvm` exactly (the
    k_x half of the per-block key).  ``xb`` is (n, batch); returns (m, batch).

    ``at_blocks`` is normally the resident programmed image from
    :func:`streamed_program_blocks` (the engine's execute-many path).
    ``at_blocks=None`` selects the *one-shot* variant: each block is encoded
    inside the scan body (consuming the k_a key half, identical draws to
    program-then-execute) and immediately consumed, so no programmed image is
    ever resident -- O(one block) memory, the dataflow of the deprecated
    :func:`streamed_corrected_mvm` shim at paper scale.

    ``grid`` / ``block_offset`` select a local window of a global block grid
    exactly as in :func:`streamed_program_blocks` (global producer indices,
    global key schedule); ``m``/``n``/``xb`` are then the LOCAL row/column
    footprint of that window -- the shard_map per-device view.  Column-partial
    psums and tier-2 denoise stay with the caller (``tier2=False``).
    """
    i0, j0 = block_offset
    oneshot = at_blocks is None
    if oneshot:
        cap_m, cap_n = cfg.geom.capacity
        mb, nb = -(-m // cap_m), -(-n // cap_n)
    else:
        mb, nb, cap_m, cap_n = at_blocks.shape
    batch = xb.shape[1]
    if cfg.ec and cfg.ec_mode not in ("fused", "faithful"):
        raise ValueError(f"unknown first-order EC mode {cfg.ec_mode!r}")
    x_pad = jnp.pad(xb, ((0, nb * cap_n - n), (0, 0)))
    x_chunks = x_pad.reshape(nb, cap_n, batch)
    keys = local_block_keys(key, mb, nb, i0, j0, grid)

    def row_step(_, row_xs):
        if oneshot:
            row_keys, i = row_xs
        else:
            at_row, row_keys, i = row_xs

        def col_step(acc, col_xs):
            if oneshot:
                k, j, x_blk = col_xs
                a_blk = _produce(block_fn, i, j)
                k_a, k_x = jax.random.split(k)
                at_blk = encode_tiled(a_blk, k_a, cfg)
            else:
                at_blk, k, j, x_blk = col_xs
                _k_a, k_x = jax.random.split(k)
                a_blk = _produce(block_fn, i, j) if cfg.ec else None
            x_t = _encode_vec(x_blk, k_x, cfg) if cfg.encode_inputs else x_blk
            with jax.named_scope("meliso.tier1"):
                if not cfg.ec:
                    return acc + matmul(at_blk, x_t), None
                if use_kernel:
                    from repro.kernels import ops as kops
                    return acc + kops.rram_ec_tile_mvm(
                        x_blk, x_t, at_blk, a_blk - at_blk), None
                if cfg.ec_mode == "faithful":
                    return acc + (matmul(at_blk, x_blk) + matmul(a_blk, x_t)
                                  - matmul(at_blk, x_t)), None
                return acc + (matmul(at_blk, x_blk)
                              + matmul(a_blk - at_blk, x_t)), None

        acc0 = jnp.zeros((cap_m, batch), jnp.float32)
        col_xs = (row_keys, j0 + jnp.arange(nb), x_chunks) if oneshot else \
            (at_row, row_keys, j0 + jnp.arange(nb), x_chunks)
        acc, _ = jax.lax.scan(col_step, acc0, col_xs)
        return None, acc

    row_xs = (keys, i0 + jnp.arange(mb)) if oneshot else \
        (at_blocks, keys, i0 + jnp.arange(mb))
    _, rows = jax.lax.scan(row_step, None, row_xs)
    p = rows.reshape(mb * cap_m, batch)[:m]
    if cfg.ec and tier2:
        p = denoise_least_square(p, lam=cfg.lam, h=cfg.h,
                                 method=cfg.denoise_method)
    return p


def streamed_block_rmvm(
    block_fn: Callable[[jax.Array, jax.Array], jnp.ndarray],
    at_blocks: Optional[jnp.ndarray],
    yb: jnp.ndarray,
    key: jax.Array,
    cfg: CrossbarConfig,
    *,
    m: int,
    n: int,
    use_kernel: bool = False,
    tier2: bool = True,
    block_offset=(0, 0),
    grid: Optional[Tuple[int, int]] = None,
) -> jnp.ndarray:
    """Scan-fused TRANSPOSED execute stage over a streamed block producer.

    The mirror of :func:`streamed_block_mvm` for ``A.T @ y``: one ``lax.scan``
    over COLUMN blocks (inner scan over row blocks -- the contraction axis of
    A^T -- with in-place fp32 accumulation) fuses the input-DAC encode of the
    row-chunked y, the per-block ``dA`` re-derivation, the transposed tier-1
    EC product (``use_kernel=True`` fuses the Pallas
    :func:`repro.kernels.ops.rram_ec_tile_rmvm` tile step) and the partial
    reduction into one traced program -- ONE device dispatch per transposed
    MVM.  Key/draw schedule matches :func:`programmed_block_rmvm` exactly
    (block (i, j) consumes the same k_x half it would in a forward
    execution).  ``yb`` is (m, batch); returns (n, batch).

    ``at_blocks=None`` selects the one-shot variant (each block re-encoded
    inside the scan with the k_a half -- draws identical to
    program-then-execute, O(one block) memory); ``grid``/``block_offset``
    select a local window of a global block grid exactly as in
    :func:`streamed_block_mvm` (``m``/``n``/``yb`` are then the LOCAL
    footprint; row-partial psums and tier-2 stay with the caller).
    """
    i0, j0 = block_offset
    oneshot = at_blocks is None
    if oneshot:
        cap_m, cap_n = cfg.geom.capacity
        mb, nb = -(-m // cap_m), -(-n // cap_n)
    else:
        mb, nb, cap_m, cap_n = at_blocks.shape
    batch = yb.shape[1]
    if cfg.ec and cfg.ec_mode not in ("fused", "faithful"):
        raise ValueError(f"unknown first-order EC mode {cfg.ec_mode!r}")
    y_pad = jnp.pad(yb, ((0, mb * cap_m - m), (0, 0)))
    y_chunks = y_pad.reshape(mb, cap_m, batch)
    # Column-major sweep over the SAME (mb, nb) key schedule: block (i, j)
    # keeps its global key whichever direction the grid is traversed.
    keys_t = jnp.swapaxes(local_block_keys(key, mb, nb, i0, j0, grid), 0, 1)
    at_t = None if oneshot else jnp.swapaxes(at_blocks, 0, 1)

    def col_step(_, col_xs):
        if oneshot:
            col_keys, j = col_xs
        else:
            at_col, col_keys, j = col_xs

        def row_step(acc, row_xs):
            if oneshot:
                k, i, y_blk = row_xs
                a_blk = _produce(block_fn, i, j)
                k_a, k_x = jax.random.split(k)
                at_blk = encode_tiled(a_blk, k_a, cfg)
            else:
                at_blk, k, i, y_blk = row_xs
                _k_a, k_x = jax.random.split(k)
                a_blk = _produce(block_fn, i, j) if cfg.ec else None
            y_t = _encode_vec(y_blk, k_x, cfg) if cfg.encode_inputs else y_blk
            with jax.named_scope("meliso.tier1"):
                if not cfg.ec:
                    return acc + matmul(at_blk.T, y_t), None
                if use_kernel:
                    from repro.kernels import ops as kops
                    return acc + kops.rram_ec_tile_rmvm(
                        y_blk, y_t, at_blk, a_blk - at_blk), None
                if cfg.ec_mode == "faithful":
                    return acc + (matmul(at_blk.T, y_blk)
                                  + matmul(a_blk.T, y_t)
                                  - matmul(at_blk.T, y_t)), None
                return acc + (matmul(at_blk.T, y_blk)
                              + matmul((a_blk - at_blk).T, y_t)), None

        acc0 = jnp.zeros((cap_n, batch), jnp.float32)
        row_xs = (col_keys, i0 + jnp.arange(mb), y_chunks) if oneshot else \
            (at_col, col_keys, i0 + jnp.arange(mb), y_chunks)
        acc, _ = jax.lax.scan(row_step, acc0, row_xs)
        return None, acc

    col_xs = (keys_t, j0 + jnp.arange(nb)) if oneshot else \
        (at_t, keys_t, j0 + jnp.arange(nb))
    _, cols = jax.lax.scan(col_step, None, col_xs)
    p = cols.reshape(nb * cap_n, batch)[:n]
    if cfg.ec and tier2:
        p = denoise_least_square(p, lam=cfg.lam, h=cfg.h,
                                 method=cfg.denoise_method)
    return p


# --------------------------------------------------------------------------- #
# Legacy one-shot entry points (deprecated shims over the two-stage dataflow)
# --------------------------------------------------------------------------- #

def corrected_mvm(
    a: jnp.ndarray,
    x: jnp.ndarray,
    key: jax.Array,
    cfg: CrossbarConfig,
) -> Tuple[jnp.ndarray, WriteStats]:
    """y ~= A @ x on the simulated multi-MCA system (paper Algorithm 6 + 4).

    .. deprecated:: use :class:`repro.engine.AnalogEngine` -- this one-shot
       form re-programs the full matrix on every call.  It remains as a shim
       over the program/execute stages for single-use MVMs and tests.

    ``x`` may be (n,) or (n, batch).  The matrix is padded, block-partitioned to
    the system capacity, each block is encoded with per-MCA scales and multiplied
    with tier-1 EC; column-block partials are summed; tier-2 denoising runs on
    the assembled local output (``denoise_scope=local`` in paper terms).
    """
    m, n = a.shape
    squeeze = x.ndim == 1
    xb = x[:, None] if squeeze else x
    at_blocks, da_blocks = program_blocks(a, key, cfg)
    p = programmed_block_mvm(at_blocks, da_blocks, xb, key, cfg, m=m, n=n)
    stats = write_cost(m, n, cfg, batch=xb.shape[1])
    return (p[:, 0] if squeeze else p), stats


def streamed_corrected_mvm(
    block_fn: Callable[[int, int], jnp.ndarray],
    x: jnp.ndarray,
    m: int,
    n: int,
    key: jax.Array,
    cfg: CrossbarConfig,
) -> Tuple[jnp.ndarray, WriteStats]:
    """Large-problem variant: ``A`` is produced block-by-block by ``block_fn(i, j)``
    (each block capacity-sized, already padded), so matrices such as the paper's
    65,025 x 65,025 case never materialize.

    .. deprecated:: use ``AnalogEngine(cfg, execution="streamed")`` -- this
       one-shot form discards the programmed tiles after a single MVM.  It is
       now a thin composition over the scan-fused pipeline: traceable
       producers run the one-shot :func:`streamed_block_mvm` variant (each
       block encoded inside the scan body and immediately consumed -- ONE
       device dispatch, O(one block) memory, so the 65,025^2 case still never
       materializes anything A-sized); opaque Python producers fall back to
       the engine's compatibility host loop (the one remaining Python block
       loop; note that path keeps the programmed image resident).  The
       per-block PRNG schedule follows the engine's ``block_keys`` split (k_a
       programs, k_x drives the input DAC), which replaces this shim's
       historical per-block ``fold_in(fold_in(key, i), j)`` draws --
       statistically identical, numerically different.
    """
    squeeze = x.ndim == 1
    xb = x[:, None] if squeeze else x
    cap_m, cap_n = cfg.geom.capacity
    if producer_is_traceable(block_fn, cap_m, cap_n):
        # Locally-scoped jit: the trace (and the producer closure it pins)
        # is garbage-collected with this call, not cached process-wide.
        run = jax.jit(partial(streamed_block_mvm, block_fn, None,
                              cfg=cfg, m=m, n=n))
        p = run(xb, key)
    else:
        from repro.engine import AnalogEngine   # deferred: engine imports us
        engine = AnalogEngine(cfg, execution="streamed")
        A = engine.program(block_fn, key, shape=(m, n))
        p = engine.mvm(A, xb, key=key)
    stats = write_cost(m, n, cfg, batch=xb.shape[1])
    return (p[:, 0] if squeeze else p), stats
