"""Distributed corrected MVM over a JAX device mesh (paper Algorithm 4).

The paper distributes chunk pairs to MPI ranks; here each mesh device owns a
2-D block of the global matrix (rows over ``row_axes``, contraction columns
over ``col_axis``) and the set of MCA tiles that block maps onto.

Placement and pipeline are orthogonal: each device's *local* stages are the
shared implementations from :mod:`repro.core.crossbar`, wrapped once in
``shard_map``.

  * **Dense placement** (:func:`make_distributed_program` /
    :func:`make_distributed_programmed_mvm`): the global operands exist and
    are block-sharded over the mesh; each device runs
    :func:`~repro.core.crossbar.local_program_dense` /
    :func:`~repro.core.crossbar.local_dense_mvm` on its resident block.
  * **Producer placement** (:func:`make_distributed_streamed_program` /
    :func:`make_distributed_streamed_mvm`): the global matrix NEVER
    materializes.  Each device derives its window of the global capacity-block
    grid from its ``(row, col)`` mesh coordinates and runs the scan-fused
    :func:`~repro.core.crossbar.streamed_program_blocks` /
    :func:`~repro.core.crossbar.streamed_block_mvm` pipelines over only its
    local blocks, with GLOBAL block indices and the GLOBAL ``block_keys``
    schedule -- so the programmed image and every DAC draw are identical,
    block for block, to the single-device streamed sweep (a 1x1 mesh is
    draw-identical to ``execution="streamed"``).

In both placements the programmed operands are written exactly once and stay
resident where they will be used, like the physical crossbars they model;
MVMs run tier-1 locally (optionally through the fused Pallas tile step -- see
:func:`pallas_shard_map_supported`), aggregate partials with ``psum`` over the
contraction axis -- the TPU-native image of the paper's MPI reduce -- and run
tier-2 denoising on-node on each device's output segment (the paper's
"on-node error correction").  The row partition stays sharded: the output is
produced already distributed, no gather required, which is what lets a whole
iterative solve (:mod:`repro.solvers`) keep its x/r/p panels sharded across
the ``lax.while_loop``.

:class:`repro.engine.AnalogEngine` with ``execution="distributed"`` is the
public interface; :func:`distributed_corrected_mvm` remains as a one-shot
deprecation shim.

Cost statistics follow the paper's Figs. 4-5 convention: energy/latency are
reported as the mean across MCAs (mean across devices here).
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .crossbar import (CrossbarConfig, input_write_cost, local_dense_mvm,
                       local_dense_rmvm, local_program_dense,
                       matrix_write_cost, streamed_block_mvm,
                       streamed_block_rmvm, streamed_program_blocks,
                       write_cost)
from .error_correction import denoise_least_square
from .write_verify import WriteStats

__all__ = [
    "distributed_corrected_mvm",
    "shard_matrix",
    "mesh_grid_shape",
    "make_distributed_program",
    "make_distributed_programmed_mvm",
    "make_distributed_rmvm",
    "make_distributed_streamed_program",
    "make_distributed_streamed_mvm",
    "make_distributed_streamed_rmvm",
    "make_distributed_group_program",
    "make_distributed_group_mvm",
    "make_distributed_group_rmvm",
    "pallas_shard_map_supported",
]


def shard_matrix(a: jnp.ndarray, mesh: Mesh, row_axis, col_axis: str):
    """Place a global (m, n) matrix block-sharded over (row_axis, col_axis)."""
    return jax.device_put(a, NamedSharding(mesh, P(row_axis, col_axis)))


def _device_key(key: jax.Array, axes: Tuple[str, ...]) -> jax.Array:
    """Decorrelate programming/DAC noise across ranks (per-device key)."""
    for ax in axes:
        key = jax.random.fold_in(key, jax.lax.axis_index(ax))
    return key


def mesh_grid_shape(mesh: Mesh, row_axes: Tuple[str, ...],
                    col_axis: str) -> Tuple[int, int]:
    """(R, C): how many ways the mesh splits rows and contraction columns."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    r = 1
    for ax in row_axes:
        r *= sizes[ax]
    return r, sizes[col_axis]


def _row_index(row_axes: Tuple[str, ...]) -> jax.Array:
    """This device's row-shard index: row-major over ``row_axes`` (in-trace)."""
    idx = jnp.int32(0)
    for ax in row_axes:
        idx = idx * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
    return idx


def _psum_partials(p: jnp.ndarray, axes) -> jnp.ndarray:
    """The psum of tier-1 partials over the contraction ``axes``, named
    ``meliso.psum`` in traces."""
    with jax.named_scope("meliso.psum"):
        return jax.lax.psum(p, axis_name=axes)


def _mean_stats(stats: WriteStats, axes: Tuple[str, ...]) -> WriteStats:
    n_ranks = jax.lax.psum(1, axis_name=axes)
    return WriteStats(
        energy_j=jax.lax.psum(stats.energy_j, axes) / n_ranks,
        latency_s=jax.lax.psum(stats.latency_s, axes) / n_ranks,
        iterations=stats.iterations,
        final_delta=stats.final_delta,
    )


def make_distributed_program(
    cfg: CrossbarConfig,
    mesh: Mesh,
    row_axes: Tuple[str, ...] = ("data",),
    col_axis: str = "model",
):
    """Build the shard_map'd program stage (unjitted, lowerable).

    Returned fn: (a (m, n), key) -> (a_tilde, da, WriteStats), with a_tilde/da
    sharded exactly like ``a`` -- the operands are written once and stay
    resident on their devices.
    """
    axes = tuple(row_axes) + (col_axis,)

    def local_fn(a_blk, key):
        k = _device_key(key, axes)
        m_loc, n_loc = a_blk.shape
        at, da = local_program_dense(a_blk, k, cfg)
        stats = _mean_stats(matrix_write_cost(m_loc, n_loc, cfg), axes)
        return at, da, stats

    row_spec = row_axes if len(row_axes) > 1 else row_axes[0]
    return shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(row_spec, col_axis), P()),
        out_specs=(P(row_spec, col_axis), P(row_spec, col_axis), P()),
    )


def make_distributed_programmed_mvm(
    cfg: CrossbarConfig,
    mesh: Mesh,
    row_axes: Tuple[str, ...] = ("data",),
    col_axis: str = "model",
    *,
    stats_include_matrix: bool = False,
    use_kernel: bool = False,
):
    """Build the shard_map'd execute stage (unjitted, lowerable).

    Returned fn: (a_tilde, da, x (n, batch), key) -> (y (m, batch) row-sharded,
    WriteStats).  Performs zero matrix-encode work: tier-1 runs against the
    resident operands via the shared per-device stage
    (:func:`~repro.core.crossbar.local_dense_mvm`; ``use_kernel=True``
    dispatches its tile products to the fused Pallas kernel -- gate on
    :func:`pallas_shard_map_supported`), partials psum over ``col_axis``,
    tier-2 denoises on-node.  ``stats_include_matrix=True`` reproduces the
    legacy one-shot accounting (programming + input writes in one figure).
    """
    axes = tuple(row_axes) + (col_axis,)

    def local_fn(at_blk, da_blk, x_blk, key):
        k = _device_key(key, axes)
        m_loc, n_loc = at_blk.shape
        batch = x_blk.shape[1]
        p = local_dense_mvm(at_blk, da_blk, x_blk, k, cfg,
                            tier2=False, use_kernel=use_kernel)
        p = _psum_partials(p, col_axis)
        if cfg.ec:
            p = denoise_least_square(
                p, lam=cfg.lam, h=cfg.h, method=cfg.denoise_method)
        if stats_include_matrix:
            stats = write_cost(m_loc, n_loc, cfg, batch=batch)
        else:
            stats = input_write_cost(m_loc, n_loc, cfg, batch=batch)
        return p, _mean_stats(stats, axes)

    row_spec = row_axes if len(row_axes) > 1 else row_axes[0]
    kwargs = {}
    if use_kernel:
        # pallas_call has no replication rule; the probe gates lowering, the
        # psum above makes the row partials exact regardless of the checker.
        kwargs["check_vma"] = False
    return shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(row_spec, col_axis), P(row_spec, col_axis),
                  P(col_axis, None), P()),
        out_specs=(P(row_spec, None), P()),
        **kwargs,
    )


def make_distributed_rmvm(
    cfg: CrossbarConfig,
    mesh: Mesh,
    row_axes: Tuple[str, ...] = ("data",),
    col_axis: str = "model",
    *,
    use_kernel: bool = False,
):
    """Build the shard_map'd TRANSPOSED execute stage (unjitted, lowerable).

    Returned fn: (a_tilde, da, y (m, batch), key) -> (z (n, batch)
    COLUMN-sharded over ``col_axis``, WriteStats).  The mirror of
    :func:`make_distributed_programmed_mvm` with the contraction flipped:
    ``y`` enters sharded over the ROW axes (the contraction axis of A^T),
    tier-1 runs transposed against the same resident operands via the shared
    per-device stage (:func:`~repro.core.crossbar.local_dense_rmvm`;
    ``use_kernel=True`` dispatches its tile products to the fused Pallas
    transposed tile step), partials psum over ``row_axes``, and tier-2
    denoises on-node on each device's COLUMN segment -- so the output is
    produced already column-sharded, ready to feed the primal update of a
    distributed PDHG iteration without a gather.
    """
    axes = tuple(row_axes) + (col_axis,)

    def local_fn(at_blk, da_blk, y_blk, key):
        k = _device_key(key, axes)
        m_loc, n_loc = at_blk.shape
        batch = y_blk.shape[1]
        p = local_dense_rmvm(at_blk, da_blk, y_blk, k, cfg,
                             tier2=False, use_kernel=use_kernel)
        p = _psum_partials(p, tuple(row_axes))
        if cfg.ec:
            p = denoise_least_square(
                p, lam=cfg.lam, h=cfg.h, method=cfg.denoise_method)
        stats = input_write_cost(m_loc, n_loc, cfg, batch=batch,
                                 transpose=True)
        return p, _mean_stats(stats, axes)

    row_spec = row_axes if len(row_axes) > 1 else row_axes[0]
    kwargs = {}
    if use_kernel:
        kwargs["check_vma"] = False
    return shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(row_spec, col_axis), P(row_spec, col_axis),
                  P(row_spec, None), P()),
        out_specs=(P(col_axis, None), P()),
        **kwargs,
    )


# --------------------------------------------------------------------------- #
# Grouped placement (a stack of same-geometry images in ONE shard_map program)
# --------------------------------------------------------------------------- #

def _scale_stats(stats: WriteStats, factor: int) -> WriteStats:
    """A group bills ``factor`` members' writes (members program in parallel
    onto disjoint MCA sets, so latency scales with energy here)."""
    return WriteStats(
        energy_j=stats.energy_j * factor,
        latency_s=stats.latency_s * factor,
        iterations=stats.iterations,
        final_delta=stats.final_delta,
    )


def make_distributed_group_program(
    cfg: CrossbarConfig,
    mesh: Mesh,
    row_axes: Tuple[str, ...] = ("data",),
    col_axis: str = "model",
):
    """Build the shard_map'd GROUP program stage (unjitted, lowerable).

    Returned fn: (a_g (g, m, n), keys (g, ...)) -> (at_g, da_g, WriteStats).
    The whole group programs in ONE shard_map dispatch: each device vmaps the
    shared :func:`~repro.core.crossbar.local_program_dense` stage over the
    leading image axis of its (g, m_loc, n_loc) resident slab, with member
    ``g`` consuming the device fold of ``keys[g]`` -- exactly the key a solo
    distributed program of that member would consume, so the stacked image is
    bit-identical to ``g`` solo programs.  Operands stay sharded over
    (``row_axes``, ``col_axis``); the image axis is never split.
    """
    axes = tuple(row_axes) + (col_axis,)

    def local_fn(a_slab, keys):
        dev_keys = jax.vmap(lambda k: _device_key(k, axes))(keys)
        size, m_loc, n_loc = a_slab.shape
        at, da = jax.vmap(lambda a, k: local_program_dense(a, k, cfg))(
            a_slab, dev_keys)
        stats = _mean_stats(
            _scale_stats(matrix_write_cost(m_loc, n_loc, cfg), size), axes)
        return at, da, stats

    row_spec = row_axes if len(row_axes) > 1 else row_axes[0]
    return shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(None, row_spec, col_axis), P()),
        out_specs=(P(None, row_spec, col_axis), P(None, row_spec, col_axis),
                   P()),
    )


def make_distributed_group_mvm(
    cfg: CrossbarConfig,
    mesh: Mesh,
    row_axes: Tuple[str, ...] = ("data",),
    col_axis: str = "model",
    *,
    use_kernel: bool = False,
):
    """Build the shard_map'd GROUP execute stage (unjitted, lowerable).

    Returned fn: (at_g, da_g, x_g (g, n, batch), keys (g, ...)) ->
    (y_g (g, m, batch) row-sharded, WriteStats).  The whole group executes in
    ONE dispatch with ONE collective: tier-1 runs vmapped over the image axis
    against the resident slabs, the stacked (g, m_loc, batch) partials psum
    over ``col_axis`` ONCE for the whole group (not once per member), and
    tier-2 denoises each member's on-node segment.  Member ``g`` under
    ``keys[g]`` is bit-identical to a solo distributed execute of that member
    under the same key.
    """
    axes = tuple(row_axes) + (col_axis,)

    def local_fn(at_slab, da_slab, x_slab, keys):
        dev_keys = jax.vmap(lambda k: _device_key(k, axes))(keys)
        size, m_loc, n_loc = at_slab.shape
        batch = x_slab.shape[2]
        p = jax.vmap(lambda at, da, x, k: local_dense_mvm(
            at, da, x, k, cfg, tier2=False, use_kernel=use_kernel))(
            at_slab, da_slab, x_slab, dev_keys)
        p = _psum_partials(p, col_axis)      # ONE psum for the group
        if cfg.ec:
            p = jax.vmap(lambda q: denoise_least_square(
                q, lam=cfg.lam, h=cfg.h, method=cfg.denoise_method))(p)
        stats = _mean_stats(
            _scale_stats(input_write_cost(m_loc, n_loc, cfg, batch=batch),
                         size), axes)
        return p, stats

    row_spec = row_axes if len(row_axes) > 1 else row_axes[0]
    kwargs = {"check_vma": False} if use_kernel else {}
    return shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(None, row_spec, col_axis), P(None, row_spec, col_axis),
                  P(None, col_axis, None), P()),
        out_specs=(P(None, row_spec, None), P()),
        **kwargs,
    )


def make_distributed_group_rmvm(
    cfg: CrossbarConfig,
    mesh: Mesh,
    row_axes: Tuple[str, ...] = ("data",),
    col_axis: str = "model",
    *,
    use_kernel: bool = False,
):
    """Build the shard_map'd GROUP transposed execute stage (unjitted).

    The :func:`make_distributed_rmvm` mirror of
    :func:`make_distributed_group_mvm`: ``y_g`` (g, m, batch) enters sharded
    over the ROW axes, transposed tier-1 runs vmapped over the image axis, the
    stacked partials psum ONCE over ``row_axes`` for the whole group, and the
    (g, n, batch) output comes back column-sharded over ``col_axis``.
    """
    axes = tuple(row_axes) + (col_axis,)

    def local_fn(at_slab, da_slab, y_slab, keys):
        dev_keys = jax.vmap(lambda k: _device_key(k, axes))(keys)
        size, m_loc, n_loc = at_slab.shape
        batch = y_slab.shape[2]
        p = jax.vmap(lambda at, da, y, k: local_dense_rmvm(
            at, da, y, k, cfg, tier2=False, use_kernel=use_kernel))(
            at_slab, da_slab, y_slab, dev_keys)
        p = _psum_partials(p, tuple(row_axes))   # ONE psum per group
        if cfg.ec:
            p = jax.vmap(lambda q: denoise_least_square(
                q, lam=cfg.lam, h=cfg.h, method=cfg.denoise_method))(p)
        stats = _mean_stats(
            _scale_stats(input_write_cost(m_loc, n_loc, cfg, batch=batch,
                                          transpose=True), size), axes)
        return p, stats

    row_spec = row_axes if len(row_axes) > 1 else row_axes[0]
    kwargs = {"check_vma": False} if use_kernel else {}
    return shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(None, row_spec, col_axis), P(None, row_spec, col_axis),
                  P(None, row_spec, None), P()),
        out_specs=(P(None, col_axis, None), P()),
        **kwargs,
    )


# --------------------------------------------------------------------------- #
# Producer-driven placement (the matrix never materializes anywhere)
# --------------------------------------------------------------------------- #

def make_distributed_streamed_program(
    block_fn: Callable[[jax.Array, jax.Array], jnp.ndarray],
    cfg: CrossbarConfig,
    mesh: Mesh,
    row_axes: Tuple[str, ...] = ("data",),
    col_axis: str = "model",
    *,
    mb: int,
    nb: int,
):
    """Build the shard_map'd producer-driven program stage (unjitted).

    Returned fn: (key,) -> at_blocks (mb, nb, cap_m, cap_n) block-sharded over
    (``row_axes``, ``col_axis``).  Each device derives its window of the
    global block grid from its mesh coordinates and runs ONE scan-fused
    :func:`~repro.core.crossbar.streamed_program_blocks` sweep over only its
    local blocks -- the source matrix is never materialized on any host or
    device, and the per-block keys come from the global ``block_keys``
    schedule so the image is identical to the single-device streamed program.
    Requires ``mb % R == 0`` and ``nb % C == 0`` (validated by the engine).
    """
    r_count, c_count = mesh_grid_shape(mesh, row_axes, col_axis)
    mb_loc, nb_loc = mb // r_count, nb // c_count

    def local_fn(key):
        i0 = _row_index(row_axes) * mb_loc
        j0 = jax.lax.axis_index(col_axis) * nb_loc
        return streamed_program_blocks(
            block_fn, key, cfg, mb_loc, nb_loc,
            block_offset=(i0, j0), grid=(mb, nb))

    row_spec = row_axes if len(row_axes) > 1 else row_axes[0]
    return shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(),),
        out_specs=P(row_spec, col_axis, None, None),
        check_vma=False,   # output varies with axis_index, not with an input
    )


def make_distributed_streamed_mvm(
    block_fn: Callable[[jax.Array, jax.Array], jnp.ndarray],
    cfg: CrossbarConfig,
    mesh: Mesh,
    row_axes: Tuple[str, ...] = ("data",),
    col_axis: str = "model",
    *,
    m: int,
    n: int,
    mb: int,
    nb: int,
    resident: bool = True,
    use_kernel: bool = False,
):
    """Build the shard_map'd producer-driven execute stage (unjitted).

    Returned fn: ``(at_blocks, x, key) -> y`` when ``resident``, else
    ``(x, key) -> y`` -- ``x`` is the global (n, batch) panel (sharded or
    resharded over ``col_axis`` on entry), ``y`` the global (m, batch) output
    which STAYS row-sharded over ``row_axes`` (no gather), so solver panels
    remain distributed across a whole ``lax.while_loop``.

    Each device runs ONE scan-fused
    :func:`~repro.core.crossbar.streamed_block_mvm` over its local window of
    the global block grid (global producer indices, global key schedule):
    input-DAC encode, per-block dA re-derivation, tier-1 EC (``use_kernel``
    fuses the Pallas tile step), fp32 row accumulation.  Tier-1 partials psum
    over ``col_axis``; tier-2 denoise runs on-node on the local output
    segment.  ``resident=False`` selects the one-shot scan variant: each
    block is re-encoded inside the scan body (draws identical to
    program-then-execute) and immediately consumed, so NO device ever holds
    more than O(one capacity block) of A -- the paper's >= 65,536^2 regime.
    """
    r_count, c_count = mesh_grid_shape(mesh, row_axes, col_axis)
    mb_loc, nb_loc = mb // r_count, nb // c_count
    cap_m, cap_n = cfg.geom.capacity
    # Local logical footprint: exact-capacity shards except on a 1-way axis,
    # where the single device owns the (possibly padded) global edge.
    m_loc = m if r_count == 1 else mb_loc * cap_m
    n_loc = n if c_count == 1 else nb_loc * cap_n

    def local_fn(*args):
        if resident:
            at_loc, x_blk, key = args
        else:
            (x_blk, key), at_loc = args, None
        i0 = _row_index(row_axes) * mb_loc
        j0 = jax.lax.axis_index(col_axis) * nb_loc
        p = streamed_block_mvm(
            block_fn, at_loc, x_blk, key, cfg, m=m_loc, n=n_loc,
            use_kernel=use_kernel, tier2=False,
            block_offset=(i0, j0), grid=(mb, nb))
        p = _psum_partials(p, col_axis)
        if cfg.ec:
            p = denoise_least_square(
                p, lam=cfg.lam, h=cfg.h, method=cfg.denoise_method)
        return p

    row_spec = row_axes if len(row_axes) > 1 else row_axes[0]
    at_spec = (P(row_spec, col_axis, None, None),) if resident else ()
    return shard_map(
        local_fn,
        mesh=mesh,
        in_specs=at_spec + (P(col_axis, None), P()),
        out_specs=P(row_spec, None),
        check_vma=False,   # axis_index-derived block windows defeat the
                           # static replication checker; psum is still exact
    )


def make_distributed_streamed_rmvm(
    block_fn: Callable[[jax.Array, jax.Array], jnp.ndarray],
    cfg: CrossbarConfig,
    mesh: Mesh,
    row_axes: Tuple[str, ...] = ("data",),
    col_axis: str = "model",
    *,
    m: int,
    n: int,
    mb: int,
    nb: int,
    resident: bool = True,
    use_kernel: bool = False,
):
    """Build the shard_map'd producer-driven TRANSPOSED execute stage.

    Returned fn: ``(at_blocks, y, key) -> z`` when ``resident``, else
    ``(y, key) -> z`` -- ``y`` the global (m, batch) panel sharded over the
    ROW axes (the contraction of A^T), ``z`` the global (n, batch) output
    which comes back COLUMN-sharded over ``col_axis`` (no gather).

    Each device runs ONE scan-fused
    :func:`~repro.core.crossbar.streamed_block_rmvm` over its window of the
    global block grid (global producer indices, global key schedule -- the
    SAME per-block k_x halves as forward execution, so a 1x1 mesh is
    draw-identical to the single-device streamed transposed sweep).
    Transposed tier-1 partials psum over ``row_axes``; tier-2 denoise runs
    on-node on the local column segment.  ``resident=False`` re-encodes each
    block inside the scan (draws identical to program-then-execute), so a
    >= 65,536^2 LP's ``A.T @ y`` runs with no device ever holding more than
    O(one capacity block) of A.
    """
    r_count, c_count = mesh_grid_shape(mesh, row_axes, col_axis)
    mb_loc, nb_loc = mb // r_count, nb // c_count
    cap_m, cap_n = cfg.geom.capacity
    m_loc = m if r_count == 1 else mb_loc * cap_m
    n_loc = n if c_count == 1 else nb_loc * cap_n

    def local_fn(*args):
        if resident:
            at_loc, y_blk, key = args
        else:
            (y_blk, key), at_loc = args, None
        i0 = _row_index(row_axes) * mb_loc
        j0 = jax.lax.axis_index(col_axis) * nb_loc
        p = streamed_block_rmvm(
            block_fn, at_loc, y_blk, key, cfg, m=m_loc, n=n_loc,
            use_kernel=use_kernel, tier2=False,
            block_offset=(i0, j0), grid=(mb, nb))
        p = _psum_partials(p, tuple(row_axes))
        if cfg.ec:
            p = denoise_least_square(
                p, lam=cfg.lam, h=cfg.h, method=cfg.denoise_method)
        return p

    row_spec = row_axes if len(row_axes) > 1 else row_axes[0]
    at_spec = (P(row_spec, col_axis, None, None),) if resident else ()
    return shard_map(
        local_fn,
        mesh=mesh,
        in_specs=at_spec + (P(row_spec, None), P()),
        out_specs=P(col_axis, None),
        check_vma=False,   # axis_index-derived block windows defeat the
                           # static replication checker; psum is still exact
    )


# Probes that passed, keyed (backend, mesh shape).
_PALLAS_PROBE_CACHE: set = set()


def pallas_shard_map_supported(mesh: Mesh) -> bool:
    """Check that the fused Pallas EC tile step lowers inside ``shard_map``.

    Compiles (never runs) a one-tile :func:`repro.kernels.ops.rram_ec_tile_mvm`
    wrapped in a trivial shard_map over ``mesh``, once per (backend, mesh
    shape), and returns True.  On the CPU backend the kernels run in
    interpret mode and this always passes; on a TPU the kernel lowers through
    Mosaic.  If it cannot lower, this raises: ``backend="pallas"`` never
    falls back to the reference tile step in silence, which would present a
    reference run as a kernel run.
    """
    cache_key = (jax.default_backend(), tuple(mesh.devices.shape))
    if cache_key in _PALLAS_PROBE_CACHE:
        return True
    from repro.kernels import ops as kops

    def local(x):
        eye = jnp.eye(8, dtype=jnp.float32)
        return kops.rram_ec_tile_mvm(x, x, eye, jnp.zeros_like(eye))

    probe = shard_map(local, mesh=mesh, in_specs=(P(),), out_specs=P(),
                      check_vma=False)
    try:
        jax.jit(probe).lower(jnp.zeros((8, 1), jnp.float32)).compile()
    except Exception as exc:
        raise RuntimeError(
            "backend='pallas' cannot lower the EC tile kernel inside "
            f"shard_map on {cache_key}; use backend='reference'") from exc
    _PALLAS_PROBE_CACHE.add(cache_key)
    return True


def distributed_corrected_mvm(
    a: jnp.ndarray,
    x: jnp.ndarray,
    key: jax.Array,
    cfg: CrossbarConfig,
    mesh: Mesh,
    row_axis: str = "data",
    col_axis: str = "model",
) -> Tuple[jnp.ndarray, WriteStats]:
    """y = A @ x with per-device multi-MCA simulation and two-tier EC.

    .. deprecated:: use ``AnalogEngine(cfg, execution="distributed",
       mesh=mesh)`` -- this one-shot form re-programs the full matrix on every
       call.  Kept as a shim composing the program and execute stages.

    ``a``: global (m, n), m divisible by mesh[row_axis], n by mesh[col_axis].
    ``x``: (n,) or (n, batch).  Output is (m,) / (m, batch), sharded over rows.
    """
    squeeze = x.ndim == 1
    xb = x[:, None] if squeeze else x
    program = make_distributed_program(cfg, mesh, (row_axis,), col_axis)
    execute = make_distributed_programmed_mvm(
        cfg, mesh, (row_axis,), col_axis, stats_include_matrix=True)

    def fused(a_, xb_, key_):
        at, da, _ = program(a_, key_)
        return execute(at, da, xb_, key_)

    y, stats = jax.jit(fused)(a, xb, key)
    return (y[:, 0] if squeeze else y), stats
