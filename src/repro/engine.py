"""Program-once / execute-many analog MVM engine (the public API).

The paper's energy win comes from writing the RRAM conductance image *once*
and amortizing it over many analog MVMs.  :class:`AnalogEngine` makes that the
API: ``engine.program(a)`` pays the write cost and returns an
:class:`AnalogMatrix` handle (the encoded per-tile image ``A_tilde``, the
tier-1 correction operand ``dA = A - A_tilde``, and the one-time
:class:`~repro.core.write_verify.WriteStats`); ``engine.mvm(A, x)`` (or simply
``A @ x``) then runs tier-1 error correction + tier-2 denoising without any
re-programming, for ``x`` of shape ``(n,)`` or ``(n, batch)``.

One ``execution=`` switch selects where the programmed image lives:

  * ``"local"``       -- dense per-capacity-block tiles on this process;
  * ``"streamed"``    -- programming consumes a ``block_fn(i, j)`` producer so
                         the source matrix never materializes (the paper's
                         65,025^2 case); the encoded tiles are kept;
  * ``"distributed"`` -- the image is placed once, block-sharded over a JAX
                         device mesh.  ``program`` accepts a dense array
                         (sharded via :func:`repro.core.distributed.shard_matrix`)
                         OR a traceable ``block_fn(i, j)`` producer, in which
                         case each device derives its window of the global
                         block grid from its mesh coordinates and scan-programs
                         only its local blocks -- the global matrix is never
                         materialized on any host or device.  MVMs run tier-1
                         locally, psum partials over the contraction axis and
                         denoise on-node; the output stays row-sharded.

Placement x pipeline matrix (which combinations fuse, which fall back)::

    execution     source      backend=reference          backend=pallas
    ------------  ----------  -------------------------  ------------------------
    local         dense a     vmapped block pipeline     fused rram_ec_matmul
                                                         (one whole-image kernel)
    streamed      traceable   ONE lax.scan dispatch per  same scan, tile step =
                  block_fn    program / MVM              rram_ec_tile_mvm kernel
    streamed      opaque      host loop, one jitted      host loop, kernel tile
                  block_fn    dispatch per block         step per block
    distributed   dense a     shard_map over the shared  shard_map'd kernel tile
                              local_dense_mvm stage      step (capability probe)
    distributed   traceable   shard_map'd scan pipeline  shard_map'd scan with
                  block_fn    per device, ONE dispatch,  the kernel tile step
                              psum partials              (capability probe)
    distributed   opaque      rejected (cannot trace inside shard_map; use
                  block_fn    execution="streamed" for the host-loop fallback)

Every cell of the matrix also executes TRANSPOSED: ``A.T @ y`` (=
:meth:`AnalogEngine.rmvm`, via the zero-copy :class:`TransposedAnalogMatrix`
view) runs the corrected ``A^T y`` against the SAME programmed image --
tier-1 ``A_tilde^T y + dA^T y_tilde`` from the stored operands, row blocks
as the contraction (psum over the mesh ROW axes under distributed execution,
output COLUMN-sharded), tier-2 denoise over the column output, the same
per-block k_x key halves as a forward call (a 1x1 mesh stays draw-identical
to streamed in both directions), and ``resident=False`` handles re-encode
inside the transposed scan exactly as they do forward (no A-sized array in
either direction).  The pallas tile step reads the same fused kernel in the
``y^T A`` direction (:func:`repro.kernels.ops.rram_ec_tile_rmvm`); see
DESIGN.md section 5.

``backend="pallas"`` under ``execution="distributed"`` is checked by
:func:`repro.core.distributed.pallas_shard_map_supported`, a compile-only
probe run once per (backend, mesh shape): where the kernel cannot lower
inside shard_map it raises -- a pallas engine never runs the reference tile
step in its place.  Producer-driven distributed programming requires the
block grid to divide evenly over the mesh (``mb % R == 0``, ``nb % C == 0``;
row/column sizes must be capacity multiples on axes split more than one
way).

``program(block_fn, ..., resident=False)`` (distributed only) keeps NO
conductance image resident: every MVM re-encodes each block inside the scan
body (draws identical to program-then-execute), so no device ever holds more
than O(one capacity block) of A -- the paper's >= 65,536^2 solves run with
zero A-sized allocations anywhere in the program (write energy is still
billed once, as the physical hardware would).

Traceable block producers (streamed execution)
----------------------------------------------

A streamed producer is *traceable* when ``block_fn(i, j)`` is a pure jax
function of the two block-index scalars: it must accept traced int32 scalars
(so only jax ops on ``i``/``j`` -- array indexing, ``jax.random.fold_in``,
arithmetic -- no ``int(i)``, host I/O, or Python control flow on the values)
and return a fixed-shape capacity-sized block.  Every procedurally generated
paper workload (e.g. :class:`repro.core.matrices.ImplicitBandedMatrix`)
qualifies.  For traceable producers the engine fuses the whole mb x nb block
sweep into single ``lax.scan`` pipelines: ``program`` is one device dispatch,
and every ``mvm`` -- input-DAC encode, per-block dA re-derivation, tier-1 EC
(the Pallas ``rram_ec_matmul`` tile step under ``backend="pallas"``), fp32
row accumulation and tier-2 denoise -- is ONE dispatch instead of mb * nb.
Solvers driving a streamed handle therefore trace into one compiled program
end-to-end.

Traceability is auto-detected with an abstract trace at ``program`` time; set
a ``block_fn.traceable = False`` attribute to force the compatibility host
loop (one jitted dispatch per block -- the pre-scan behavior), which is also
what opaque producers (ones that fail the abstract trace) fall back to.

and a ``backend=`` switch dispatches the inner product:

  * ``"reference"`` -- pure-jnp blockwise oracle (always available);
  * ``"pallas"``    -- the fused TPU kernel :func:`repro.kernels.rram_ec_matmul`
                       plus the tier-2 stencil/Thomas kernels (interpret mode
                       on CPU).

Usage::

    import jax, jax.numpy as jnp
    from repro.core import CrossbarConfig, MCAGeometry, get_device
    from repro.engine import AnalogEngine

    cfg = CrossbarConfig(device=get_device("taox-hfox"),
                         geom=MCAGeometry(1, 1, 66, 66), k_iters=5, ec=True)
    engine = AnalogEngine(cfg)
    A = engine.program(a, jax.random.PRNGKey(1))   # one-time write
    print(A.write_stats.energy_j)                  # programming cost, paid once
    y1 = A @ x1                                    # corrected MVMs: no encode
    y2 = A @ x2                                    #   work, only the x DAC pass
    y, call_stats = engine.mvm_with_stats(A, x3)   # per-call input-write cost

The legacy one-shot entry points (``corrected_mvm``,
``streamed_corrected_mvm``, ``distributed_corrected_mvm``) remain as thin
deprecation shims over the same two-stage dataflow.

Solver entry points
-------------------

:mod:`repro.solvers` builds iterative linear solves on top of this engine --
the workload the program-once model exists for (MELISO+ is an in-memory
linear SOlver).  Every method touches the programmed image only through
``engine.mvm``, so it works across all execution modes and backends::

    from repro import solvers
    A = engine.program(a, key)                  # one-time write
    solvers.cg(A, b, tol=1e-4)                  # SPD Krylov solve
    solvers.richardson(A, b)                    # auto-omega stationary solve
    solvers.gmres(A, b); solvers.bicgstab(A, b) # general matrices
    solvers.refine(A, b)                        # analog inner + digital outer
    solvers.pdhg(A, b, c)                       # LP: min c'x, Ax=b, x>=0
                                                #   (matvec + rmatvec per iter)

Each returns a :class:`~repro.solvers.SolveResult` whose ledger splits energy
into this handle's one-time ``write_stats`` and the accumulated per-MVM
``input_write_stats`` -- the amortization curve of Figs. 4-5.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Callable, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import crossbar
from repro.core.crossbar import CrossbarConfig
from repro.core.error_correction import denoise_least_square
from repro.core.write_verify import WriteStats

__all__ = ["AnalogEngine", "AnalogMatrix", "AnalogMatrixGroup",
           "TransposedAnalogMatrix", "EXECUTION_MODES", "BACKENDS",
           "SCAN_CACHE_MAX", "CHAIN_ACTIVATIONS"]

EXECUTION_MODES = ("local", "streamed", "distributed")
BACKENDS = ("reference", "pallas")

#: The host span (``jax.profiler.TraceAnnotation``) around each solo or group
#: execute, from entry to the return of the jitted call; its arguments are
#: ``path`` (``"<execution>/<backend>"``), ``direction``, ``cols``, on a
#: distributed engine ``mesh`` (:attr:`AnalogEngine.mesh_label`, ``"2x2"``)
#: and, where a tier-1 kernel runs, ``tier1`` (``"vpu"``, ``"mxu_packed"``
#: or ``"mxu"``, :func:`repro.kernels.ops.tier1_form`).  It records nothing
#: unless the profiler is tracing.
SPAN_EXECUTE = "meliso.engine.execute"
_DIRECTION = {False: "forward", True: "transposed"}

#: Per-handle bound on cached jitted execute pipelines.  Long-lived serving
#: handles see many (backend, direction, batch-bucket) combinations; each
#: cached entry pins a compiled XLA executable, so an unbounded dict is a
#: slow leak.  The cache is an LRU keyed BY batch size (among other things):
#: evicting an entry drops its jit object and every trace inside it.
SCAN_CACHE_MAX = 8

#: Static elementwise nonlinearities :meth:`AnalogEngine.chain_mvm` may fuse
#: between chained group members (None = pure linear chain).
CHAIN_ACTIVATIONS = {
    None: lambda x: x,
    "relu": jax.nn.relu,
    "tanh": jnp.tanh,
    "gelu": jax.nn.gelu,
}


class _BoundedCache:
    """Tiny LRU for per-handle jitted pipelines (see :data:`SCAN_CACHE_MAX`).

    Dropping an entry releases the jit wrapper -- and with it every compiled
    trace it held -- so a handle that cycles through many batch buckets keeps
    at most ``maxsize`` live executables instead of growing without bound.
    """

    def __init__(self, maxsize: int = SCAN_CACHE_MAX):
        self.maxsize = maxsize
        self._entries: "collections.OrderedDict" = collections.OrderedDict()

    def get(self, key):
        fn = self._entries.get(key)
        if fn is not None:
            self._entries.move_to_end(key)
        return fn

    def put(self, key, fn) -> None:
        self._entries[key] = fn
        self._entries.move_to_end(key)
        while len(self._entries) > self.maxsize:
            self._entries.popitem(last=False)

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key) -> bool:
        return key in self._entries


def _scan_cache(handle) -> _BoundedCache:
    """The handle's bounded pipeline cache, created on first use."""
    if not isinstance(handle._scan_exec, _BoundedCache):
        handle._scan_exec = _BoundedCache()
    return handle._scan_exec


def _traced(x) -> bool:
    """Whether ``x`` is a tracer, i.e. the caller is inside a jit trace (or
    another transformation) where host-side state must not advance."""
    return isinstance(x, jax.core.Tracer)


def _scale_stats(stats: WriteStats, factor: float) -> WriteStats:
    """``factor`` members' worth of one member's :class:`WriteStats`."""
    return WriteStats(
        energy_j=stats.energy_j * factor,
        latency_s=stats.latency_s * factor,
        iterations=stats.iterations,
        final_delta=stats.final_delta,
    )


@dataclasses.dataclass
class AnalogMatrix:
    """Handle to a matrix programmed onto the (simulated) analog hardware.

    Holds the per-tile conductance image and tier-1 correction operand in the
    layout of its engine's execution mode, the one-time programming
    :class:`WriteStats`, and the base PRNG key whose per-block ``k_x`` halves
    drive the input DAC noise of successive executions.
    """

    engine: "AnalogEngine"
    shape: Tuple[int, int]
    base_key: jax.Array
    write_stats: WriteStats
    # local / streamed layout: (mb, nb, cap_m, cap_n) stacked capacity tiles.
    at_blocks: Optional[jnp.ndarray] = None
    da_blocks: Optional[jnp.ndarray] = None
    # streamed layout keeps the producer instead of materializing da_blocks,
    # so the resident state is exactly the programmed image (1x, not 2x).
    block_fn: Optional[Callable[[int, int], jnp.ndarray]] = None
    # whether block_fn traced as a pure jax function of the index scalars
    # (scan-fused single-dispatch pipelines) or needs the host loop.
    block_traceable: bool = False
    # distributed dense layout: (m, n) arrays block-sharded over the mesh.
    at_dense: Optional[jnp.ndarray] = None
    da_dense: Optional[jnp.ndarray] = None
    # producer-driven distributed layout: at_blocks is the global (mb, nb,
    # cap_m, cap_n) block array sharded over the mesh (None for
    # resident=False handles, which re-encode inside every MVM's scan).
    mesh_sharded: bool = False
    # device-lifetime state (repro.reliability): when an AgeLedger is
    # attached (reliability.aging.attach_age), every execute applies the aged
    # image -- drift + replayable stuck-at faults -- inside the SAME jitted
    # dispatch, and host-side executes advance the per-block MVM count.
    age: Optional["object"] = None
    calls: int = 0
    # per-handle jitted scan pipelines: a _BoundedCache LRU keyed by
    # (backend, direction, batch bucket), built on first execute; dies with
    # the handle -- see the jit-scoping note below.
    _scan_exec: Optional["_BoundedCache"] = None

    @property
    def m(self) -> int:
        return self.shape[0]

    @property
    def n(self) -> int:
        return self.shape[1]

    def _grid(self) -> Tuple[int, int]:
        """(mb, nb) capacity-block grid of this handle."""
        if self.at_blocks is not None:
            return self.at_blocks.shape[:2]
        cap_m, cap_n = self.engine.cfg.geom.capacity
        return -(-self.m // cap_m), -(-self.n // cap_n)

    @property
    def a_tilde(self) -> jnp.ndarray:
        """The programmed conductance image, dense and unpadded (m, n).

        An explicitly materializing view: for non-resident (``resident=False``)
        distributed handles it re-derives the image with one scanned sweep.
        """
        if self.at_dense is not None:
            return self.at_dense
        if self.at_blocks is not None:
            return _assemble(self.at_blocks, self.m, self.n)
        mb, nb = self._grid()
        at = jax.jit(functools.partial(
            crossbar.streamed_program_blocks, self.block_fn,
            cfg=self.engine.cfg, mb=mb, nb=nb))(self.base_key)
        return _assemble(at, self.m, self.n)

    @property
    def da(self) -> jnp.ndarray:
        """The tier-1 correction operand A - A_tilde, dense unpadded (m, n)."""
        if self.da_dense is not None:
            return self.da_dense
        if self.da_blocks is not None:
            return _assemble(self.da_blocks, self.m, self.n)
        if self.at_blocks is not None:
            return _assemble(self._producer_blocks() - self.at_blocks,
                             self.m, self.n)
        return self.dense() - self.a_tilde

    def dense(self) -> jnp.ndarray:
        """The exact source matrix A = A_tilde + dA, dense unpadded (m, n).

        For streamed handles this skips the A_tilde/dA round trip entirely:
        A_tilde + (block - A_tilde) == block, so one producer sweep suffices.
        """
        if self.at_dense is not None:
            return self.at_dense + self.da_dense
        if self.da_blocks is not None:
            return _assemble(self.at_blocks + self.da_blocks, self.m, self.n)
        return _assemble(self._producer_blocks(), self.m, self.n)

    def _producer_blocks(self) -> jnp.ndarray:
        """All producer blocks, (mb, nb, cap_m, cap_n): one scanned dispatch
        for traceable producers, a host loop for opaque ones."""
        mb, nb = self._grid()
        if self.block_traceable:
            return jax.jit(functools.partial(
                crossbar.produce_blocks, self.block_fn, mb, nb))()
        return jnp.stack([jnp.stack([self.block_fn(i, j) for j in range(nb)])
                          for i in range(mb)])

    def __matmul__(self, x: jnp.ndarray) -> jnp.ndarray:
        return self.engine.mvm(self, x)

    @property
    def T(self) -> "TransposedAnalogMatrix":
        """Zero-copy transposed view: ``A.T @ y`` runs the corrected
        TRANSPOSED MVM ``A^T y`` against the SAME programmed image (no
        re-encode, no second handle -- the crossbar is read backwards)."""
        return TransposedAnalogMatrix(self)

    def input_write_stats(self, batch: int = 1) -> WriteStats:
        """Per-execution write cost (x DAC pass + EC X^T replica)."""
        return self.engine.input_write_stats(self, batch)

    @property
    def image_nbytes(self) -> int:
        """Resident bytes of this handle's programmed operands.

        Counts the stored image/correction layout (blocks or dense); every
        backend executes from that layout in place, and block_fn producers
        are code, not residency, and count zero.  This is the unit the
        serving :class:`~repro.serving.cache.ImageCache` budgets in."""
        total = 0
        for arr in (self.at_blocks, self.da_blocks, self.at_dense,
                    self.da_dense):
            if arr is not None and hasattr(arr, "nbytes"):
                total += int(arr.nbytes)
        return total

    def release(self) -> None:
        """Drop the jitted execute pipelines cached on the handle.  The
        programmed image itself survives -- eviction of the image is the
        cache owner dropping its reference to the whole handle."""
        self._scan_exec = None


@dataclasses.dataclass(frozen=True)
class TransposedAnalogMatrix:
    """Transposed view of an :class:`AnalogMatrix` (``A.T``).

    Holds NO operands of its own: every execution reads the parent's
    programmed conductance image in the transposed direction through
    :meth:`AnalogEngine.rmvm` (tier-1 ``A_tilde^T y + dA^T y_tilde``,
    row-block partials summed, tier-2 denoise over the column output), so the
    one-time write cost is shared with the forward view and a PDHG-style
    solver alternating ``A @ x`` / ``A.T @ y`` programs the matrix exactly
    once.  ``A.T.T is A``.
    """

    parent: AnalogMatrix

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.parent.shape[1], self.parent.shape[0])

    @property
    def m(self) -> int:
        return self.shape[0]

    @property
    def n(self) -> int:
        return self.shape[1]

    @property
    def T(self) -> AnalogMatrix:
        return self.parent

    @property
    def engine(self) -> "AnalogEngine":
        return self.parent.engine

    @property
    def write_stats(self) -> WriteStats:
        """The parent's one-time programming cost (shared, never re-paid)."""
        return self.parent.write_stats

    def __matmul__(self, y: jnp.ndarray) -> jnp.ndarray:
        return self.parent.engine.rmvm(self.parent, y)

    def dense(self) -> jnp.ndarray:
        """The exact transposed source matrix A^T, dense unpadded (n, m)."""
        return self.parent.dense().T

    def input_write_stats(self, batch: int = 1) -> WriteStats:
        """Per-execution cost of one transposed MVM (y DAC pass + EC Y^T
        replica over the row dimension)."""
        return self.parent.engine.input_write_stats(self.parent, batch,
                                                    transpose=True)


@dataclasses.dataclass
class AnalogMatrixGroup:
    """A stack of same-geometry programmed images executed as ONE dispatch.

    Built by :meth:`AnalogEngine.program_group` (a pytree of same-shape
    matrices or a tuple of traceable producers) or :meth:`AnalogEngine.group`
    (stacking existing compatible handles).  The ``size`` member images share
    one stacked layout along a leading image axis; every execute --
    :meth:`AnalogEngine.group_mvm`, :meth:`~AnalogEngine.group_rmvm`,
    :meth:`~AnalogEngine.chain_mvm` -- runs the whole group in a single
    device dispatch, so an L-layer analog model costs O(1) launches instead
    of O(L).  Member ``g`` draws exactly what a solo handle programmed with
    ``member_keys[g]`` draws: grouping changes the dispatch count, never the
    key schedule.  ``group()``-built stacks carry the solo images bit-exactly;
    ``program_group``'s fused encode agrees with the eager per-member path to
    float32 rounding (XLA may reassociate the vmapped arithmetic).  See
    DESIGN.md section 13.
    """

    engine: "AnalogEngine"
    size: int
    shape: Tuple[int, int]          # per-member (m, n)
    base_key: jax.Array
    member_keys: jax.Array          # stacked per-member base keys, leading g
    write_stats: WriteStats         # total across all members
    # local / streamed layout: (g, mb, nb, cap_m, cap_n) stacked tiles.
    at_blocks: Optional[jnp.ndarray] = None
    da_blocks: Optional[jnp.ndarray] = None
    # streamed layout: one traceable producer per member (dA re-derived per
    # block inside the grouped scan; da_blocks stays None).
    block_fns: Optional[Tuple[Callable, ...]] = None
    # distributed dense layout: (g, m, n) stacked arrays, each member
    # block-sharded over the mesh (leading axis replicated).
    at_dense: Optional[jnp.ndarray] = None
    da_dense: Optional[jnp.ndarray] = None
    mesh_sharded: bool = False
    # stacked AgeLedger (leading g on every field) attached by
    # repro.reliability.aging.attach_group_age: the grouped execute ages
    # every member inside the same single dispatch.
    ages: Optional["object"] = None
    calls: int = 0
    _scan_exec: Optional["_BoundedCache"] = None

    @property
    def m(self) -> int:
        return self.shape[0]

    @property
    def n(self) -> int:
        return self.shape[1]

    def _grid(self) -> Tuple[int, int]:
        """(mb, nb) capacity-block grid of every member."""
        if self.at_blocks is not None:
            return self.at_blocks.shape[1:3]
        cap_m, cap_n = self.engine.cfg.geom.capacity
        return -(-self.m // cap_m), -(-self.n // cap_n)

    def member(self, g: int) -> AnalogMatrix:
        """Member ``g`` as a standalone :class:`AnalogMatrix` view.

        Slices the stacked operands (no copy beyond the slice); the view
        executes through the solo paths with the member's own base key and
        a proportional share of the group's one-time write cost.
        """
        if not 0 <= g < self.size:
            raise IndexError(f"member {g} of a size-{self.size} group")
        stats = _scale_stats(self.write_stats, 1.0 / self.size)
        if self.at_dense is not None:
            return AnalogMatrix(
                engine=self.engine, shape=self.shape,
                base_key=self.member_keys[g], write_stats=stats,
                at_dense=self.at_dense[g], da_dense=self.da_dense[g],
                mesh_sharded=True)
        return AnalogMatrix(
            engine=self.engine, shape=self.shape,
            base_key=self.member_keys[g], write_stats=stats,
            at_blocks=self.at_blocks[g],
            da_blocks=None if self.da_blocks is None else self.da_blocks[g],
            block_fn=None if self.block_fns is None else self.block_fns[g],
            block_traceable=self.block_fns is not None)

    def __matmul__(self, x: jnp.ndarray) -> jnp.ndarray:
        return self.engine.group_mvm(self, x)

    def input_write_stats(self, batch: int = 1,
                          *, transpose: bool = False) -> WriteStats:
        """Per-execution input-write cost of the WHOLE group (``size``
        members' DAC passes + EC replicas)."""
        one = self.engine.input_write_stats(self, batch, transpose=transpose)
        return _scale_stats(one, self.size)

    @property
    def image_nbytes(self) -> int:
        """Resident bytes of the stacked operands."""
        total = 0
        for arr in (self.at_blocks, self.da_blocks, self.at_dense,
                    self.da_dense):
            if arr is not None and hasattr(arr, "nbytes"):
                total += int(arr.nbytes)
        return total

    def release(self) -> None:
        """Drop the jitted grouped pipelines cached on the group; the
        programmed stack stays."""
        self._scan_exec = None


_assemble = crossbar.assemble_blocks
_program_local = jax.jit(crossbar.program_blocks, static_argnames=("cfg",))


@functools.partial(jax.jit, static_argnames=("cfg", "m", "n"))
def _exec_reference(at_blocks, da_blocks, xb, key, *, cfg, m, n):
    return crossbar.programmed_block_mvm(
        at_blocks, da_blocks, xb, key, cfg, m=m, n=n)


@functools.partial(jax.jit, static_argnames=("cfg", "m", "n"))
def _exec_reference_t(at_blocks, da_blocks, yb, key, *, cfg, m, n):
    return crossbar.programmed_block_rmvm(
        at_blocks, da_blocks, yb, key, cfg, m=m, n=n)


@functools.partial(jax.jit, static_argnames=("cfg", "m", "n", "transpose"))
def _exec_reference_aged(at_blocks, da_blocks, xb, key, age, *, cfg, m, n,
                         transpose):
    """Aged execute: ONE dispatch containing the aging transform AND the
    corrected MVM.  The physical image drifts / latches
    (:func:`repro.reliability.aging.aged_blocks`) while the stored tier-1
    operand ``dA`` stays as measured at program time, so the corrected
    product honestly degrades with age instead of silently self-correcting.
    """
    from repro.reliability.aging import aged_blocks
    at_aged = aged_blocks(at_blocks, age, cfg.device)
    run = crossbar.programmed_block_rmvm if transpose \
        else crossbar.programmed_block_mvm
    return run(at_aged, da_blocks, xb, key, cfg, m=m, n=n)


def _pallas_corrected(at, da, xb, key, cfg, m, n, transpose):
    """Shared Pallas execute body (unjitted; used solo-jitted and grouped).

    ``at``/``da`` are the stored (mb, nb, cap_m, cap_n) capacity-block
    stacks, which the kernel reads in place: no dense or transposed copy of
    the image is ever made.  The kernel path encodes the input with a single
    DAC pass (one noise draw for the whole padded vector -- fold 1 of the
    call key forward, fold 2 transposed, keeping the directions distinct
    when a caller reuses a key) instead of the reference path's
    per-(block, chunk) draws -- statistically identical, one kernel launch:
    ``At x + dA xt`` forward, ``z^T = y^T At + yt^T dA`` backwards through
    the same operands.
    """
    from repro.kernels import ops as kops

    mp, np_ = kops.matrix_shape(at)
    pad_to = mp if transpose else np_
    with jax.named_scope("meliso.dac"):
        x_pad = jnp.pad(xb, ((0, pad_to - xb.shape[0]), (0, 0)))
        if cfg.encode_inputs:
            fold = 2 if transpose else 1
            x_t = crossbar._encode_vec(x_pad, jax.random.fold_in(key, fold),
                                       cfg)
        else:
            x_t = x_pad
    with jax.named_scope("meliso.tier1"):
        if cfg.ec:
            if transpose:
                p = kops.rram_ec_matmul(x_pad.T, x_t.T, at, da,
                                        transposed=True).T[:n]
            else:
                p = kops.rram_ec_matmul(at, da, x_pad, x_t)[:m]
        else:
            dense = _assemble(at, mp, np_)
            p = crossbar.matmul(dense.T, x_t)[:n] if transpose \
                else crossbar.matmul(dense, x_t)[:m]
    if cfg.ec:
        if cfg.denoise_method == "neumann":
            p = kops.denoise_stencil(p, lam=cfg.lam, h=cfg.h)
        elif cfg.denoise_method == "thomas":
            p = kops.denoise_thomas(p, lam=cfg.lam, h=cfg.h)
        else:
            p = denoise_least_square(p, lam=cfg.lam, h=cfg.h,
                                     method=cfg.denoise_method)
    return p


@functools.partial(jax.jit, static_argnames=("cfg", "m", "n"))
def _exec_pallas(at, da, xb, key, *, cfg, m, n):
    """Tier-1 via the fused Pallas EC kernel + tier-2 via the solver kernels
    (see :func:`_pallas_corrected`); ``at``/``da`` are the handle's stored
    capacity-block stacks."""
    return _pallas_corrected(at, da, xb, key, cfg, m, n, transpose=False)


@functools.partial(jax.jit, static_argnames=("cfg", "m", "n"))
def _exec_pallas_t(at, da, yb, key, *, cfg, m, n):
    """Transposed tier-1 via the same fused Pallas EC kernel read backwards
    over the same stored block stacks."""
    return _pallas_corrected(at, da, yb, key, cfg, m, n, transpose=True)


@functools.partial(jax.jit, static_argnames=("cfg", "m", "n", "transpose"))
def _exec_group_reference(at_g, da_g, xb_g, keys, *, cfg, m, n, transpose):
    """Grouped execute: every member's corrected MVM in ONE dispatch (the
    vmapped :func:`repro.core.crossbar.grouped_block_mvm` stage; member g
    consumes ``keys[g]`` exactly as its solo execute would)."""
    run = crossbar.grouped_block_rmvm if transpose \
        else crossbar.grouped_block_mvm
    return run(at_g, da_g, xb_g, keys, cfg, m=m, n=n)


@functools.partial(jax.jit, static_argnames=("cfg", "m", "n", "transpose"))
def _exec_group_pallas(at_g, da_g, xb_g, keys, *, cfg, m, n, transpose):
    """Grouped Pallas execute: ONE dispatch, one ``lax.map`` over members,
    each running the fused whole-image EC kernel body with its own key --
    member g's draws are identical to its solo :func:`_exec_pallas` call."""
    def one(ops):
        at, da, xb, k = ops
        return _pallas_corrected(at, da, xb, k, cfg, m, n, transpose)

    return jax.lax.map(one, (at_g, da_g, xb_g, keys))


@functools.partial(jax.jit, static_argnames=("cfg", "m", "n", "transpose"))
def _exec_group_reference_aged(at_g, da_g, xb_g, keys, ages, *, cfg, m, n,
                               transpose):
    """Grouped AGED execute: one dispatch containing every member's aging
    transform (drift + replayable stuck-at faults, per member ledger) AND the
    grouped corrected MVM -- aging adds zero dispatches to a group exactly as
    it adds zero to a solo handle (DESIGN.md section 12)."""
    from repro.reliability.aging import aged_blocks
    at_aged = jax.vmap(lambda at, age: aged_blocks(at, age, cfg.device))(
        at_g, ages)
    run = crossbar.grouped_block_rmvm if transpose \
        else crossbar.grouped_block_mvm
    return run(at_aged, da_g, xb_g, keys, cfg, m=m, n=n)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "m", "n", "activation",
                                    "use_kernel"))
def _exec_chain(at_g, da_g, x0, keys, *, cfg, m, n, activation, use_kernel):
    """Whole-model chained forward: ONE ``lax.scan`` over the image axis
    threads the activation through every member -- an L-layer analog MLP
    forward is a single device dispatch.  Member g's corrected MVM consumes
    ``keys[g]`` (the same per-block k_x halves as its solo execute); the
    static ``activation`` from :data:`CHAIN_ACTIVATIONS` applies between
    members."""
    act = CHAIN_ACTIVATIONS[activation]

    def body(x, ops):
        at, da, k = ops
        y = crossbar.programmed_block_mvm(at, da, x, k, cfg, m=m, n=n,
                                          use_kernel=use_kernel)
        return act(y), None

    y, _ = jax.lax.scan(body, x0, (at_g, da_g, keys))
    return y


# Scan-fused streamed pipelines: the pure stages live in
# :mod:`repro.core.crossbar` (streamed_program_blocks / streamed_block_mvm /
# produce_blocks); jit scoping is deliberate.  Program-time and da/dense
# sweeps use locally-scoped jits (one trace per call, garbage-collected with
# it); the execute-many hot path caches its jitted pipeline ON THE HANDLE
# (:attr:`AnalogMatrix._scan_exec`), so a warm streamed MVM re-invokes the
# producer zero times yet the trace -- and the producer closure it pins --
# dies with the handle instead of accumulating in a process-wide cache.


class AnalogEngine:
    """Program-once / execute-many corrected-MVM engine.

    Parameters
    ----------
    cfg:
        The :class:`CrossbarConfig` describing one multi-MCA system (for
        ``execution="distributed"``: the per-device system).
    execution:
        ``"local"`` | ``"streamed"`` | ``"distributed"``.
    backend:
        ``"reference"`` (pure jnp) | ``"pallas"`` (fused TPU kernels; interpret
        mode on CPU).  Under ``execution="distributed"`` the Pallas tile step
        runs inside ``shard_map``; the capability probe
        (:func:`repro.core.distributed.pallas_shard_map_supported`) raises
        where it cannot lower.
    mesh, row_axes, col_axis:
        Mesh placement for ``execution="distributed"``: rows shard over
        ``row_axes``, the contraction over ``col_axis``.
    """

    def __init__(
        self,
        cfg: CrossbarConfig,
        *,
        execution: str = "local",
        backend: str = "reference",
        mesh=None,
        row_axes: Tuple[str, ...] = ("data",),
        col_axis: str = "model",
    ):
        if execution not in EXECUTION_MODES:
            raise ValueError(
                f"unknown execution mode {execution!r}; expected one of "
                f"{EXECUTION_MODES}")
        if backend not in BACKENDS:
            raise ValueError(
                f"unknown backend {backend!r}; expected one of {BACKENDS}")
        if execution == "distributed" and mesh is None:
            raise ValueError("execution='distributed' requires a mesh")
        self.cfg = cfg
        self.execution = execution
        self.backend = backend
        self.mesh = mesh
        self.row_axes = tuple(row_axes)
        self.col_axis = col_axis
        self._streamed_step = {}        # jitted per-block host-loop steps,
                                        # keyed (use_kernel, transpose)
        if execution == "distributed":
            from repro.core import distributed as D
            self._dist_program = jax.jit(D.make_distributed_program(
                cfg, mesh, self.row_axes, col_axis))
            self._dist_mvm = jax.jit(D.make_distributed_programmed_mvm(
                cfg, mesh, self.row_axes, col_axis))
            # dense execute pipelines keyed by (use_kernel, transpose)
            # (pallas / transposed variants built lazily, the former behind
            # the shard_map capability probe).
            self._dist_mvm_cache = {(False, False): self._dist_mvm}

    def _dist_use_kernel(self) -> bool:
        """Whether distributed execution may fuse the Pallas tile step."""
        if self.backend != "pallas" or not self.cfg.ec:
            return False
        from repro.core import distributed as D
        return D.pallas_shard_map_supported(self.mesh)

    def _dense_dist_exec(self, transpose: bool = False):
        """The jitted shard_map'd dense execute stage for this backend
        (forward or transposed)."""
        use_kernel = self._dist_use_kernel()
        fn = self._dist_mvm_cache.get((use_kernel, transpose))
        if fn is None:
            from repro.core import distributed as D
            make = D.make_distributed_rmvm if transpose else \
                D.make_distributed_programmed_mvm
            fn = jax.jit(make(
                self.cfg, self.mesh, self.row_axes, self.col_axis,
                use_kernel=use_kernel))
            self._dist_mvm_cache[(use_kernel, transpose)] = fn
        return fn

    # ------------------------------------------------------------- programming
    def program(
        self,
        a: Union[jnp.ndarray, Callable[[int, int], jnp.ndarray]],
        key: jax.Array,
        *,
        shape: Optional[Tuple[int, int]] = None,
        resident: bool = True,
    ) -> AnalogMatrix:
        """Write ``a`` onto the analog system once; returns the reusable handle.

        ``a`` is a dense (m, n) array, or -- for ``execution="streamed"`` and
        ``execution="distributed"`` -- a ``block_fn(i, j)`` producer of
        capacity-sized (already padded) blocks, in which case ``shape=(m, n)``
        gives the logical problem size.  Producers that trace as pure jax
        functions of the index scalars (see the module docstring) are
        programmed and executed as single-dispatch ``lax.scan`` pipelines
        (mesh-sharded windows of the global block grid under distributed
        execution); opaque producers take a host loop per block (streamed
        only -- distributed execution rejects them).

        ``resident=False`` (distributed producers only) keeps no conductance
        image: each MVM re-encodes blocks inside its scan with the identical
        draws, so no device ever allocates more than one capacity block of A.
        """
        if callable(a) and not hasattr(a, "shape"):
            if self.execution not in ("streamed", "distributed"):
                raise ValueError("a block_fn producer requires "
                                 "execution='streamed' or 'distributed'")
            if shape is None:
                raise ValueError("program(block_fn, ...) requires shape=(m, n)")
            if self.execution == "distributed":
                return self._program_distributed_streamed(
                    a, shape, key, resident)
            if not resident:
                raise ValueError("resident=False requires "
                                 "execution='distributed' (streamed handles "
                                 "keep the programmed image)")
            return self._program_streamed(a, shape, key)
        if not resident:
            raise ValueError(
                "resident=False requires a block_fn producer under "
                "execution='distributed'")
        m, n = a.shape
        if self.execution == "distributed":
            return self._program_distributed(a, key)
        at_blocks, da_blocks = _program_local(a, key, cfg=self.cfg)
        return AnalogMatrix(
            engine=self, shape=(m, n), base_key=key,
            write_stats=crossbar.matrix_write_cost(m, n, self.cfg),
            at_blocks=at_blocks, da_blocks=da_blocks)

    def _program_streamed(self, block_fn, shape, key) -> AnalogMatrix:
        m, n = shape
        cap_m, cap_n = self.cfg.geom.capacity
        mb, nb = -(-m // cap_m), -(-n // cap_n)
        traceable = crossbar.producer_is_traceable(block_fn, cap_m, cap_n)
        if traceable:
            # One scanned dispatch programs every capacity block (local jit:
            # programming runs once per handle, no process-wide cache entry).
            at_blocks = jax.jit(functools.partial(
                crossbar.streamed_program_blocks, block_fn,
                cfg=self.cfg, mb=mb, nb=nb))(key)
        else:
            # Compatibility host loop: one jitted dispatch per block.
            keys = crossbar.block_keys(key, mb, nb)

            def enc(blk, k):
                k_a, _ = jax.random.split(k)
                return crossbar.encode_tiled(blk, k_a, self.cfg)

            step = jax.jit(enc)
            at_blocks = jnp.stack(
                [jnp.stack([step(block_fn(i, j), keys[i, j])
                            for j in range(nb)])
                 for i in range(mb)])
        # Only the programmed image is kept resident (the simulated hardware
        # state); the tier-1 operand dA is re-derived per block at execute
        # time from the producer, so huge matrices are never held twice.
        return AnalogMatrix(
            engine=self, shape=(m, n), base_key=key,
            write_stats=crossbar.matrix_write_cost(m, n, self.cfg),
            at_blocks=at_blocks, block_fn=block_fn,
            block_traceable=traceable)

    def _program_distributed(self, a, key) -> AnalogMatrix:
        from repro.core import distributed as D
        m, n = a.shape
        row_spec = self.row_axes if len(self.row_axes) > 1 else self.row_axes[0]
        a_sh = D.shard_matrix(a, self.mesh, row_spec, self.col_axis)
        at, da, stats = self._dist_program(a_sh, key)
        return AnalogMatrix(
            engine=self, shape=(m, n), base_key=key, write_stats=stats,
            at_dense=at, da_dense=da, mesh_sharded=True)

    def _program_distributed_streamed(self, block_fn, shape, key,
                                      resident) -> AnalogMatrix:
        """Producer-driven distributed programming: each device scan-programs
        its window of the global block grid; A never materializes anywhere."""
        from repro.core import distributed as D
        m, n = shape
        cap_m, cap_n = self.cfg.geom.capacity
        mb, nb = -(-m // cap_m), -(-n // cap_n)
        if not crossbar.producer_is_traceable(block_fn, cap_m, cap_n):
            raise ValueError(
                "execution='distributed' requires a traceable block_fn "
                "producer (a pure jax function of the two index scalars): "
                "opaque producers cannot run inside shard_map -- use "
                "execution='streamed' for the host-loop fallback")
        n_row, n_col = D.mesh_grid_shape(self.mesh, self.row_axes,
                                         self.col_axis)
        if mb % n_row or nb % n_col:
            raise ValueError(
                f"the {mb} x {nb} capacity-block grid does not divide over "
                f"the {n_row} x {n_col} mesh; pick a capacity/mesh so every "
                "device owns an equal block window")
        if n_row > 1 and m != mb * cap_m:
            raise ValueError(
                f"m={m} must be a multiple of the capacity row size {cap_m} "
                "to row-shard a producer grid (produce padded blocks and "
                "declare the padded shape)")
        if n_col > 1 and n != nb * cap_n:
            raise ValueError(
                f"n={n} must be a multiple of the capacity column size "
                f"{cap_n} to column-shard a producer grid")
        at_blocks = None
        if resident:
            # ONE jitted dispatch programs every device's block window.
            prog = jax.jit(D.make_distributed_streamed_program(
                block_fn, self.cfg, self.mesh, self.row_axes, self.col_axis,
                mb=mb, nb=nb))
            at_blocks = prog(key)
        # Per-device footprint; mean across the uniform shards == per-device
        # value (the Figs. 4-5 reporting convention).
        m_loc = m if n_row == 1 else (mb // n_row) * cap_m
        n_loc = n if n_col == 1 else (nb // n_col) * cap_n
        return AnalogMatrix(
            engine=self, shape=(m, n), base_key=key,
            write_stats=crossbar.matrix_write_cost(m_loc, n_loc, self.cfg),
            at_blocks=at_blocks, block_fn=block_fn, block_traceable=True,
            mesh_sharded=True)

    def encode_dense(self, a: jnp.ndarray, key: jax.Array) -> jnp.ndarray:
        """The programmed image of ``a`` as a dense unpadded array.

        Pure jax function of (a, key): safe under jit/vmap (used by
        :func:`repro.models.rram.program_rram` for stacked layer kernels).
        """
        at_blocks, _ = crossbar.program_blocks(a, key, self.cfg)
        return _assemble(at_blocks, *a.shape)

    # ------------------------------------------------------ group programming
    def program_group(
        self,
        source,
        key: jax.Array,
        *,
        shape: Optional[Tuple[int, int]] = None,
    ) -> AnalogMatrixGroup:
        """Program a whole stack of matrices as ONE grouped dispatch.

        ``source`` is a pytree of same-shape 2-D arrays (list, dict, nested
        -- the leaves stack in ``jax.tree_util`` leaf order), a single
        pre-stacked (g, m, n) array, or -- under ``execution="streamed"`` --
        a sequence of traceable ``block_fn(i, j)`` producers with
        ``shape=(m, n)``.  Member ``g`` is programmed with
        ``fold_in(key, g)`` and its image is bit-identical to a solo
        ``program`` under that key; only the dispatch count changes (one
        launch for the whole group instead of one per member).  Under
        ``execution="distributed"`` the stack programs in one ``shard_map``
        with each member block-sharded over the mesh.
        """
        leaves = jax.tree_util.tree_leaves(source)
        if not leaves:
            raise ValueError("program_group needs at least one member")
        producers = [f for f in leaves
                     if callable(f) and not hasattr(f, "shape")]
        if producers and len(producers) != len(leaves):
            raise ValueError(
                "program_group members must be all arrays or all block_fn "
                "producers, not a mix")
        if producers:
            return self._program_group_streamed(tuple(producers), key, shape)
        if len(leaves) == 1 and getattr(leaves[0], "ndim", 0) == 3:
            stack = jnp.asarray(leaves[0])
        else:
            shapes = sorted({tuple(getattr(l, "shape", ())) for l in leaves})
            if len(shapes) != 1 or len(shapes[0]) != 2:
                raise ValueError(
                    "program_group needs geometry-compatible members: every "
                    f"leaf must be the same 2-D (m, n) shape, got {shapes} "
                    "(group same-shape kernels; program the rest solo)")
            stack = jnp.stack([jnp.asarray(l) for l in leaves])
        size, m, n = stack.shape
        member_keys = jax.vmap(
            lambda g: jax.random.fold_in(key, g))(jnp.arange(size))
        if self.execution == "distributed":
            return self._program_group_distributed(stack, key, member_keys)
        at_g, da_g = jax.jit(functools.partial(
            crossbar.group_program_blocks, cfg=self.cfg))(stack, member_keys)
        stats = _scale_stats(crossbar.matrix_write_cost(m, n, self.cfg), size)
        return AnalogMatrixGroup(
            engine=self, size=size, shape=(m, n), base_key=key,
            member_keys=member_keys, write_stats=stats,
            at_blocks=at_g, da_blocks=da_g)

    def _program_group_streamed(self, block_fns, key, shape
                                ) -> AnalogMatrixGroup:
        if self.execution == "distributed":
            raise ValueError(
                "program_group does not take producer groups under "
                "execution='distributed' (one producer already scan-programs "
                "the whole mesh); program members individually or use "
                "execution='streamed'")
        if self.execution != "streamed":
            raise ValueError(
                "a producer group requires execution='streamed'")
        if shape is None:
            raise ValueError(
                "program_group(producers, ...) requires shape=(m, n)")
        m, n = shape
        cap_m, cap_n = self.cfg.geom.capacity
        mb, nb = -(-m // cap_m), -(-n // cap_n)
        for g, fn in enumerate(block_fns):
            if not crossbar.producer_is_traceable(fn, cap_m, cap_n):
                raise ValueError(
                    f"group member {g}'s block_fn is not traceable: grouped "
                    "streamed execution selects producers by lax.switch "
                    "inside one scan, so every member must trace as a pure "
                    "jax function of the index scalars (program opaque "
                    "producers individually instead)")
        size = len(block_fns)
        member_keys = jax.vmap(
            lambda g: jax.random.fold_in(key, g))(jnp.arange(size))
        at_g = jax.jit(functools.partial(
            crossbar.grouped_streamed_program_blocks, block_fns,
            cfg=self.cfg, mb=mb, nb=nb))(member_keys)
        stats = _scale_stats(crossbar.matrix_write_cost(m, n, self.cfg), size)
        return AnalogMatrixGroup(
            engine=self, size=size, shape=(m, n), base_key=key,
            member_keys=member_keys, write_stats=stats,
            at_blocks=at_g, block_fns=block_fns)

    def _program_group_distributed(self, stack, key, member_keys
                                   ) -> AnalogMatrixGroup:
        from repro.core import distributed as D
        size, m, n = stack.shape
        row_spec = self.row_axes if len(self.row_axes) > 1 else self.row_axes[0]
        a_sh = jax.device_put(stack, NamedSharding(
            self.mesh, P(None, row_spec, self.col_axis)))
        prog = self._dist_mvm_cache.get("group_program")
        if prog is None:
            prog = jax.jit(D.make_distributed_group_program(
                self.cfg, self.mesh, self.row_axes, self.col_axis))
            self._dist_mvm_cache["group_program"] = prog
        at_g, da_g, stats = prog(a_sh, member_keys)
        return AnalogMatrixGroup(
            engine=self, size=size, shape=(m, n), base_key=key,
            member_keys=member_keys, write_stats=stats,
            at_dense=at_g, da_dense=da_g, mesh_sharded=True)

    def group(self, handles: Sequence[AnalogMatrix]) -> AnalogMatrixGroup:
        """Stack already-programmed compatible handles into a group.

        No re-programming: the members' images stack verbatim (member ``g``
        of the group is bit-identical to ``handles[g]``), so grouped
        execution of existing handles gives the single-dispatch pipeline for
        free.  Members must share one engine configuration and one (m, n)
        shape, hold resident LOCAL images (dense blocks, or all-streamed with
        traceable producers), and carry no attached :class:`AgeLedger` --
        attach ages to the GROUP via
        :func:`repro.reliability.aging.attach_group_age` instead.
        """
        handles = list(handles)
        if not handles:
            raise ValueError("group() needs at least one handle")
        shapes = sorted({h.shape for h in handles})
        if len(shapes) != 1:
            raise ValueError(
                "group() members must be geometry-compatible (one shared "
                f"(m, n) shape); got {shapes}")
        for g, h in enumerate(handles):
            if isinstance(h, TransposedAnalogMatrix):
                raise ValueError(
                    "group() stacks forward handles; run the transposed "
                    "direction through group_rmvm")
            if h.engine is not self and h.engine.cfg != self.cfg:
                raise ValueError(
                    f"group() member {g} was programmed by an incompatible "
                    "engine configuration")
            if h.mesh_sharded or h.at_dense is not None:
                raise ValueError(
                    "group() stacks local handles; distributed images group "
                    "at program time via program_group")
            if h.at_blocks is None:
                raise ValueError(
                    f"group() member {g} holds no resident image "
                    "(resident=False handles cannot be grouped)")
            if h.age is not None:
                raise ValueError(
                    f"group() member {g} has an AgeLedger attached; group "
                    "first, then age the group via attach_group_age")
        streamed = [h.da_blocks is None for h in handles]
        if any(streamed):
            if not all(streamed):
                raise ValueError(
                    "group() members must be all dense or all streamed")
            if not all(h.block_traceable for h in handles):
                raise ValueError(
                    "grouped streamed execution requires every member's "
                    "producer to be traceable")
            block_fns = tuple(h.block_fn for h in handles)
            da_g = None
        else:
            block_fns = None
            da_g = jnp.stack([h.da_blocks for h in handles])
        at_g = jnp.stack([h.at_blocks for h in handles])
        member_keys = jnp.stack([h.base_key for h in handles])
        total = WriteStats(
            energy_j=sum(h.write_stats.energy_j for h in handles),
            latency_s=sum(h.write_stats.latency_s for h in handles),
            iterations=handles[0].write_stats.iterations,
            final_delta=max(h.write_stats.final_delta for h in handles))
        return AnalogMatrixGroup(
            engine=self, size=len(handles), shape=handles[0].shape,
            base_key=handles[0].base_key, member_keys=member_keys,
            write_stats=total, at_blocks=at_g, da_blocks=da_g,
            block_fns=block_fns)

    # --------------------------------------------------------------- execution
    def mvm(self, A: AnalogMatrix, x: jnp.ndarray, *,
            key: Optional[jax.Array] = None) -> jnp.ndarray:
        """Corrected MVM against the programmed image: zero re-encode work.

        ``x``: (n,) or (n, batch).  ``key`` overrides the input-DAC noise key;
        by default successive calls consume fresh folds of the handle's base
        key (call 0 reproduces the legacy one-shot draws exactly).
        """
        y, _ = self._execute(A, x, key)
        return y

    def mvm_with_stats(self, A: AnalogMatrix, x: jnp.ndarray, *,
                       key: Optional[jax.Array] = None
                       ) -> Tuple[jnp.ndarray, WriteStats]:
        """Like :meth:`mvm` but also returns this call's input-write cost."""
        return self._execute(A, x, key, with_stats=True)

    def rmvm(self, A: AnalogMatrix, y: jnp.ndarray, *,
             key: Optional[jax.Array] = None) -> jnp.ndarray:
        """Corrected TRANSPOSED MVM ``A.T @ y`` against the programmed image.

        ``y``: (m,) or (m, batch); returns (n,) / (n, batch).  Reads the SAME
        conductance image as :meth:`mvm` -- zero re-encode, zero extra
        programming cost; only the y vector passes through the DAC (per
        row-block chunk, consuming the identical per-block k_x key halves a
        forward call would) and tier-2 denoising runs over the column output.
        Under ``execution="distributed"`` the row shards are the contraction
        axis: partials psum over the ROW axes and the output comes back
        COLUMN-sharded (over ``col_axis``).  ``A.T @ y`` is the operator
        form; :class:`TransposedAnalogMatrix` documents the view.
        """
        z, _ = self._execute(A, y, key, transpose=True)
        return z

    def rmvm_with_stats(self, A: AnalogMatrix, y: jnp.ndarray, *,
                        key: Optional[jax.Array] = None
                        ) -> Tuple[jnp.ndarray, WriteStats]:
        """Like :meth:`rmvm` but also returns this call's input-write cost."""
        return self._execute(A, y, key, with_stats=True, transpose=True)

    # --------------------------------------------------------- group execution
    def group_mvm(self, G: AnalogMatrixGroup, x: jnp.ndarray, *,
                  key: Optional[jax.Array] = None) -> jnp.ndarray:
        """Corrected MVM of EVERY group member in one device dispatch.

        ``x`` broadcasts or distributes over the image axis:

        * ``(n,)`` / ``(n, batch)`` -- the same input to every member;
        * ``(size, n)`` / ``(size, n, batch)`` -- one input per member
          (a shape that is both -- square ``size == n`` 2-D input --
          resolves per-member).

        Returns ``(size, m)`` / ``(size, m, batch)``.  ``key`` seeds member
        ``g``'s DAC draws with ``fold_in(key, g)``; by default successive
        calls consume per-member folds of ``member_keys`` -- member ``g``'s
        call ``c`` draws match a solo handle's call ``c`` exactly.
        """
        y, _ = self._group_execute(G, x, key)
        return y

    def group_mvm_with_stats(self, G: AnalogMatrixGroup, x: jnp.ndarray, *,
                             key: Optional[jax.Array] = None
                             ) -> Tuple[jnp.ndarray, WriteStats]:
        """Like :meth:`group_mvm` plus the whole group's input-write cost."""
        return self._group_execute(G, x, key, with_stats=True)

    def group_rmvm(self, G: AnalogMatrixGroup, y: jnp.ndarray, *,
                   key: Optional[jax.Array] = None) -> jnp.ndarray:
        """Corrected TRANSPOSED MVM of every member in one dispatch
        (``A_g.T @ y_g`` against the same stacked image; ``y``: ``(m,)``,
        ``(m, batch)``, ``(size, m)`` or ``(size, m, batch)``)."""
        z, _ = self._group_execute(G, y, key, transpose=True)
        return z

    def group_rmvm_with_stats(self, G: AnalogMatrixGroup, y: jnp.ndarray, *,
                              key: Optional[jax.Array] = None
                              ) -> Tuple[jnp.ndarray, WriteStats]:
        """Like :meth:`group_rmvm` plus the group's input-write cost."""
        return self._group_execute(G, y, key, with_stats=True, transpose=True)

    def chain_mvm(self, G: AnalogMatrixGroup, x: jnp.ndarray, *,
                  key: Optional[jax.Array] = None,
                  activation: Optional[str] = None) -> jnp.ndarray:
        """Whole-model CHAINED forward in one dispatch: member 0's output
        feeds member 1's input and so on -- an L-layer analog forward pass is
        a single ``lax.scan`` launch.  Members must be square (``m == n``);
        ``activation`` (a :data:`CHAIN_ACTIVATIONS` name or None) applies
        between members inside the same dispatch.  ``x``: (n,) or (n, batch).
        """
        if isinstance(G, AnalogMatrix):
            raise TypeError("chain_mvm takes an AnalogMatrixGroup; wrap solo "
                            "handles with engine.group([...])")
        if G.m != G.n:
            raise ValueError(
                f"chain_mvm threads each member's output into the next, so "
                f"members must be square; the group is {G.m} x {G.n}")
        if activation not in CHAIN_ACTIVATIONS:
            names = sorted(k for k in CHAIN_ACTIVATIONS if k is not None)
            raise ValueError(
                f"unknown chain activation {activation!r}; expected None or "
                f"one of {names}")
        if G.at_blocks is None or G.da_blocks is None:
            raise ValueError(
                "chain_mvm needs a LOCAL resident group (dense members with "
                "stacked at/da blocks)")
        if G.ages is not None:
            raise ValueError("chain_mvm does not apply attached ages; "
                             "detach them or use group_mvm")
        squeeze = x.ndim == 1
        xb = x[:, None] if squeeze else x
        if xb.shape[0] != G.n:
            raise ValueError(
                f"chain_mvm: input has {xb.shape[0]} rows but the members "
                f"are {G.m} x {G.n}")
        keys = self._group_keys(G, key, xb)
        G.calls += 1
        use_kernel = self.backend == "pallas" and self.cfg.ec
        y = _exec_chain(G.at_blocks, G.da_blocks, xb, keys, cfg=self.cfg,
                        m=G.m, n=G.n, activation=activation,
                        use_kernel=use_kernel)
        return y[:, 0] if squeeze else y

    def _group_keys(self, G: AnalogMatrixGroup, key, x) -> jax.Array:
        """Per-member execute keys: explicit ``key`` fans out as
        ``fold_in(key, g)``; the default schedule folds each member's base
        key by the call counter, matching the solo per-handle schedule
        draw-for-draw."""
        if key is not None:
            return jax.vmap(lambda g: jax.random.fold_in(key, g))(
                jnp.arange(G.size))
        if _traced(x):
            raise ValueError(
                "engine.group_mvm inside jit needs an explicit key= (the "
                "default call-counter key schedule is host-side state)")
        if G.calls == 0:
            return G.member_keys
        return jax.vmap(lambda k: jax.random.fold_in(k, G.calls))(
            G.member_keys)

    def _group_input(self, G, x, transpose):
        """Normalize group input to (size, contraction, batch) + output mode."""
        contraction = G.m if transpose else G.n
        direction = "G.T @ y" if transpose else "G @ x"
        if x.ndim == 1:
            if x.shape[0] != contraction:
                raise ValueError(
                    f"{direction}: input has {x.shape[0]} rows but members "
                    f"are {G.m} x {G.n}")
            return jnp.broadcast_to(x[None, :, None],
                                    (G.size, contraction, 1)), True
        if x.ndim == 2:
            if x.shape == (G.size, contraction):
                return x[:, :, None], True
            if x.shape[0] == contraction:
                return jnp.broadcast_to(x[None], (G.size,) + x.shape), False
            raise ValueError(
                f"{direction}: 2-D input must be ({contraction}, batch) or "
                f"(size={G.size}, {contraction}); got {x.shape}")
        if x.ndim == 3:
            if x.shape[0] != G.size or x.shape[1] != contraction:
                raise ValueError(
                    f"{direction}: 3-D input must be (size={G.size}, "
                    f"{contraction}, batch); got {x.shape}")
            return x, False
        raise ValueError(f"{direction}: input must be 1-, 2- or 3-D")

    def tier1_form(self, cols: int, transpose: bool = False):
        """The form of the tier-1 kernel an execute of ``cols`` input columns
        runs (:func:`repro.kernels.ops.tier1_form`), or None where none runs:
        the reference backend, no EC, or a mesh that cannot host the kernel."""
        if self.backend != "pallas" or not self.cfg.ec or (
                self.execution == "distributed"
                and not self._dist_use_kernel()):
            return None
        from repro.kernels import ops as kops
        return kops.tier1_form(cols, transposed=transpose)

    def _span_cols(self, span, cols: int, transpose: bool) -> None:
        """Record ``cols``, the tier-1 form and the mesh on the execute
        span."""
        form = self.tier1_form(cols, transpose)
        mesh = self.mesh_label
        span.set_metadata(cols=cols,
                          **({} if form is None else {"tier1": form}),
                          **({} if mesh is None else {"mesh": mesh}))

    def _execute_span(self, transpose: bool):
        """The host span :data:`SPAN_EXECUTE` of one execute."""
        return jax.profiler.TraceAnnotation(
            SPAN_EXECUTE, path=f"{self.execution}/{self.backend}",
            direction=_DIRECTION[transpose])

    def _group_execute(self, G, x, key, with_stats=False, transpose=False):
        with self._execute_span(transpose) as span:
            return self._group_run(G, x, key, with_stats, transpose, span)

    def _group_run(self, G, x, key, with_stats, transpose, span):
        if not isinstance(G, AnalogMatrixGroup):
            raise TypeError("group_mvm takes an AnalogMatrixGroup; use "
                            "engine.mvm for solo handles")
        if G.engine is not self and G.engine.cfg != self.cfg:
            raise ValueError("AnalogMatrixGroup was programmed by an "
                             "incompatible engine configuration")
        if self.execution == "distributed":
            if G.at_dense is None:
                raise ValueError(
                    "this engine executes distributed but the group holds "
                    "block tiles; build it with the distributed engine's "
                    "program_group")
        elif G.at_blocks is None:
            raise ValueError(
                "the group holds mesh-sharded operands but this engine "
                f"executes {self.execution!r}; build it with this engine")
        xb, squeeze = self._group_input(G, x, transpose)
        self._span_cols(span, xb.shape[2], transpose)
        keys = self._group_keys(G, key, x)
        G.calls += 1
        m, n = G.shape
        batch = xb.shape[2]
        stats = None
        if self.execution == "distributed":
            p, stats = self._group_dist_exec(transpose)(
                G.at_dense, G.da_dense, xb, keys)
        elif G.ages is not None:
            if self.backend != "reference" or G.da_blocks is None:
                raise ValueError(
                    "aged group execution needs execution='local', "
                    "backend='reference' and resident da blocks")
            p = _exec_group_reference_aged(
                G.at_blocks, G.da_blocks, xb, keys, G.ages,
                cfg=self.cfg, m=m, n=n, transpose=transpose)
            if not _traced(p):
                G.ages = G.ages.advanced(1)
        elif G.da_blocks is None:
            # Streamed group: dA re-derived per block from each member's
            # producer inside one grouped scan pipeline.
            use_kernel = self.backend == "pallas" and self.cfg.ec
            cache = _scan_cache(G)
            cache_key = (use_kernel, transpose, batch)
            fn = cache.get(cache_key)
            if fn is None:
                stage = crossbar.grouped_streamed_block_rmvm if transpose \
                    else crossbar.grouped_streamed_block_mvm
                fn = jax.jit(functools.partial(
                    stage, G.block_fns,
                    cfg=self.cfg, m=m, n=n, use_kernel=use_kernel))
                cache.put(cache_key, fn)
            p = fn(G.at_blocks, xb, keys)
        elif self.backend == "pallas":
            p = _exec_group_pallas(G.at_blocks, G.da_blocks, xb, keys,
                                   cfg=self.cfg, m=m, n=n,
                                   transpose=transpose)
        else:
            p = _exec_group_reference(G.at_blocks, G.da_blocks, xb, keys,
                                      cfg=self.cfg, m=m, n=n,
                                      transpose=transpose)
        if with_stats and stats is None:
            stats = G.input_write_stats(batch, transpose=transpose)
        return (p[:, :, 0] if squeeze else p), stats

    def _group_dist_exec(self, transpose: bool = False):
        """The jitted shard_map'd GROUP execute stage for this backend."""
        use_kernel = self._dist_use_kernel()
        fn = self._dist_mvm_cache.get(("group", use_kernel, transpose))
        if fn is None:
            from repro.core import distributed as D
            make = D.make_distributed_group_rmvm if transpose else \
                D.make_distributed_group_mvm
            fn = jax.jit(make(
                self.cfg, self.mesh, self.row_axes, self.col_axis,
                use_kernel=use_kernel))
            self._dist_mvm_cache[("group", use_kernel, transpose)] = fn
        return fn

    # ------------------------------------------------------- analysis hooks
    def mvm_fn(self, A: AnalogMatrix, *, transpose: bool = False):
        """Traceable ``(vec, key) -> out`` closure over a programmed handle.

        The canonical pipeline surface for jaxpr-level tooling: the
        invariant registry (:mod:`repro.analysis.pipelines`) traces these
        closures with ``ShapeDtypeStruct`` placeholders, so the verifier
        passes see exactly the computation :meth:`mvm` / :meth:`rmvm`
        dispatch.  See DESIGN.md section 10.
        """
        if transpose:
            return lambda y, key: self.rmvm(A, y, key=key)
        return lambda x, key: self.mvm(A, x, key=key)

    def group_mvm_fn(self, G: AnalogMatrixGroup, *, transpose: bool = False):
        """Traceable ``(vec, key) -> out`` closure over a grouped handle --
        the :meth:`mvm_fn` analogue the invariant registry traces to pin the
        whole group to ONE top-level dispatch."""
        if transpose:
            return lambda y, key: self.group_rmvm(G, y, key=key)
        return lambda x, key: self.group_mvm(G, x, key=key)

    def chain_fn(self, G: AnalogMatrixGroup, *,
                 activation: Optional[str] = None):
        """Traceable closure over the chained whole-model forward
        (:meth:`chain_mvm`)."""
        return lambda x, key: self.chain_mvm(G, x, key=key,
                                             activation=activation)

    @property
    def mesh_label(self) -> Optional[str]:
        """The mesh a distributed execution spans, as its shape (``"2x2"``);
        None for single-device modes."""
        if self.execution != "distributed":
            return None
        return "x".join(str(s) for s in self.mesh.devices.shape)

    @property
    def collective_axes(self) -> Tuple[str, ...]:
        """Mesh axes a distributed execution may legally reduce over
        (the CollectiveAudit whitelist); empty for single-device modes."""
        if self.execution != "distributed":
            return ()
        return (*self.row_axes, self.col_axis)

    def input_write_stats(self, A: AnalogMatrix, batch: int = 1,
                          *, transpose: bool = False) -> WriteStats:
        """Per-execution input-write cost, in the same reporting convention as
        the handle's ``write_stats`` (distributed: mean across devices, the
        paper's Figs. 4-5 convention).  Non-divisible mesh shapes bill the
        ceil-divided per-device footprint -- the rows/cols a real placement
        would pad onto the largest shard -- instead of silently flooring.
        ``transpose=True`` bills a transposed execution (the m-length y DAC
        pass + the row-dimension EC replica)."""
        m, n = A.shape
        if self.execution == "distributed":
            sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
            for ax in self.row_axes:
                m = -(-m // sizes[ax])
            n = -(-n // sizes[self.col_axis])
        return crossbar.input_write_cost(m, n, self.cfg, batch=batch,
                                         transpose=transpose)

    def _execute(self, A, x, key, with_stats=False, transpose=False):
        if isinstance(A, AnalogMatrixGroup):
            raise TypeError("engine.mvm/rmvm take a solo AnalogMatrix; "
                            "use engine.group_mvm/group_rmvm for groups")
        if isinstance(A, TransposedAnalogMatrix):
            # A transposed view executes as the opposite direction of its
            # parent: (A.T).T @ x is a forward MVM of the parent.  The same
            # cross-engine guard as the direct path applies BEFORE
            # delegating, so a view can't smuggle a handle past it.
            if A.parent.engine is not self and A.parent.engine.cfg != self.cfg:
                raise ValueError(
                    "AnalogMatrix was programmed by an incompatible "
                    "engine configuration")
            return A.parent.engine._execute(A.parent, x, key,
                                            with_stats=with_stats,
                                            transpose=not transpose)
        with self._execute_span(transpose) as span:
            return self._execute_solo(A, x, key, with_stats, transpose, span)

    def _execute_solo(self, A, x, key, with_stats, transpose, span):
        if A.engine is not self and A.engine.cfg != self.cfg:
            raise ValueError("AnalogMatrix was programmed by an incompatible "
                             "engine configuration")
        if self.execution == "distributed":
            # Only handles programmed BY a distributed engine may execute
            # here: producer handles from a streamed engine skipped the
            # mesh/grid validation (mb % R, capacity multiples, traceability)
            # and would mis-shape or die opaquely inside shard_map.
            if A.at_dense is None and not (A.block_fn is not None
                                           and A.mesh_sharded):
                raise ValueError(
                    "AnalogMatrix holds block tiles but this engine executes "
                    "distributed; program it with the distributed engine")
        elif A.at_blocks is None or A.mesh_sharded:
            raise ValueError(
                "AnalogMatrix holds mesh-sharded operands but this engine "
                f"executes {self.execution!r}; program it with this engine")
        squeeze = x.ndim == 1
        xb = x[:, None] if squeeze else x
        self._span_cols(span, xb.shape[1], transpose)
        contraction = A.m if transpose else A.n
        if xb.shape[0] != contraction:
            direction = "A.T @ y" if transpose else "A @ x"
            raise ValueError(
                f"{direction}: input has {xb.shape[0]} rows but the "
                f"programmed matrix is {A.m} x {A.n}")
        if key is None:
            # The default key schedule advances Python-side per call; under a
            # jit trace it would freeze at its trace-time value and every
            # execution would reuse identical DAC noise -- require an explicit
            # key there instead of silently correlating the draws.
            if _traced(xb):
                raise ValueError(
                    "engine.mvm inside jit needs an explicit key= (the "
                    "default call-counter key schedule is host-side state)")
            key = A.base_key if A.calls == 0 else \
                jax.random.fold_in(A.base_key, A.calls)
        A.calls += 1
        m, n = A.shape
        if self.execution == "distributed":
            if A.at_dense is not None:
                p, stats = self._dense_dist_exec(transpose)(
                    A.at_dense, A.da_dense, xb, key)
            else:
                # Producer-driven: ONE shard_map'd scan dispatch, output
                # stays row-sharded (column-sharded for transposed calls);
                # per-call cost is analytic (the same ceil-divided per-device
                # mean as input_write_stats).
                p = self._exec_dist_streamed(A, xb, key, transpose)
                stats = self.input_write_stats(A, xb.shape[1],
                                               transpose=transpose) \
                    if with_stats else None
        else:
            stats = None
            if A.age is not None and A.da_blocks is not None \
                    and self.backend == "reference":
                # Aged execute: drift + stuck-at faults applied to the image
                # inside the one jitted dispatch (DESIGN.md section 12).
                p = _exec_reference_aged(A.at_blocks, A.da_blocks, xb, key,
                                         A.age, cfg=self.cfg, m=m, n=n,
                                         transpose=transpose)
                # Host-dispatched executes age the image by one read disturb
                # per call; traced executes (inside a solver's jit) advance
                # the ledger explicitly via A.age = A.age.advanced(mvms).
                if not _traced(p):
                    A.age = A.age.advanced(1)
            elif A.age is not None:
                raise ValueError(
                    "an AgeLedger is attached but this execution path cannot "
                    "apply it: aged execution needs execution='local', "
                    "backend='reference' and resident at/da blocks")
            elif A.da_blocks is None:
                # Streamed handle: dA is not resident; re-derive per block.
                p = self._exec_streamed(A, xb, key, transpose)
            elif self.backend == "pallas":
                run = _exec_pallas_t if transpose else _exec_pallas
                p = run(A.at_blocks, A.da_blocks, xb, key,
                        cfg=self.cfg, m=m, n=n)
            else:
                run = _exec_reference_t if transpose else _exec_reference
                p = run(A.at_blocks, A.da_blocks, xb, key,
                        cfg=self.cfg, m=m, n=n)
        if with_stats and stats is None:
            stats = crossbar.input_write_cost(m, n, self.cfg,
                                              batch=xb.shape[1],
                                              transpose=transpose)
        return (p[:, 0] if squeeze else p), stats

    def _exec_streamed(self, A, xb, key, transpose=False):
        """Streamed execute: dA = block_fn - A_tilde is re-derived per
        capacity block (O(block) extra memory), so the streamed path never
        holds the source matrix twice.  Traceable producers run the
        scan-fused single-dispatch pipeline (forward or transposed); opaque
        ones take the compatibility host loop (one jitted dispatch per
        block)."""
        cfg = self.cfg
        if cfg.ec and cfg.ec_mode not in ("fused", "faithful"):
            raise ValueError(f"unknown first-order EC mode {cfg.ec_mode!r}")
        m, n = A.shape
        use_kernel = self.backend == "pallas" and cfg.ec
        if A.block_traceable:
            # Bounded LRU keyed INCLUDING the batch size: each jit object
            # holds exactly one compiled batch bucket, so a long-lived
            # serving handle cycling through buckets keeps at most
            # SCAN_CACHE_MAX live executables (eviction drops the jit object
            # and every trace inside it) instead of growing per
            # (backend, direction, batch) without bound.
            cache = _scan_cache(A)
            cache_key = (use_kernel, transpose, xb.shape[1])
            fn = cache.get(cache_key)
            if fn is None:
                stage = crossbar.streamed_block_rmvm if transpose \
                    else crossbar.streamed_block_mvm
                fn = jax.jit(functools.partial(
                    stage, A.block_fn,
                    cfg=cfg, m=m, n=n, use_kernel=use_kernel))
                cache.put(cache_key, fn)
            return fn(A.at_blocks, xb, key)
        return self._exec_streamed_host(A, xb, key, use_kernel, transpose)

    def _exec_dist_streamed(self, A, xb, key, transpose=False):
        """Producer-driven distributed execute: each device runs the
        scan-fused streamed pipeline over its window of the global block
        grid (one dispatch), partials psum over the contraction axis (the
        column axis forward, the ROW axes transposed), tier-2 denoises
        on-node, and the output stays sharded over the non-contracted axis.
        The jitted shard_map pipeline is cached on the handle per backend
        and direction, so solver loops re-enter a warm trace."""
        use_kernel = self._dist_use_kernel()
        cache = _scan_cache(A)
        cache_key = ("dist", use_kernel, A.at_blocks is not None, transpose,
                     xb.shape[1])
        fn = cache.get(cache_key)
        if fn is None:
            from repro.core import distributed as D
            m, n = A.shape
            mb, nb = A._grid()
            make = D.make_distributed_streamed_rmvm if transpose else \
                D.make_distributed_streamed_mvm
            fn = jax.jit(make(
                A.block_fn, self.cfg, self.mesh, self.row_axes, self.col_axis,
                m=m, n=n, mb=mb, nb=nb, resident=A.at_blocks is not None,
                use_kernel=use_kernel))
            cache.put(cache_key, fn)
        # Place the input as the shard_map takes it.  Its type then carries
        # the mesh on every call, so a solver's first input and its loop
        # carries (shard_map outputs) hit one trace of the pipeline.
        row_spec = self.row_axes if len(self.row_axes) > 1 \
            else self.row_axes[0]
        xb = jax.lax.with_sharding_constraint(xb, NamedSharding(
            self.mesh, P(row_spec if transpose else self.col_axis, None)))
        if A.at_blocks is not None:
            return fn(A.at_blocks, xb, key)
        return fn(xb, key)

    def _exec_streamed_host(self, A, xb, key, use_kernel, transpose=False):
        """The compat-only Python block loop (the one remaining in the repo):
        O(mb * nb) dispatches per MVM, kept for producers that cannot trace.
        Same per-block keys, draws and tile math as the scanned pipelines,
        in either direction (``transpose`` chunks the input over row blocks
        and accumulates over them -- the contraction axis of A^T)."""
        cfg = self.cfg
        m, n = A.shape
        mb, nb, cap_m, cap_n = A.at_blocks.shape
        batch = xb.shape[1]
        pad_to = mb * cap_m if transpose else nb * cap_n
        x_pad = jnp.pad(xb, ((0, pad_to - xb.shape[0]), (0, 0)))
        x_chunks = x_pad.reshape(mb if transpose else nb, -1, batch)
        keys = crossbar.block_keys(key, mb, nb)

        step = self._streamed_step.get((use_kernel, transpose))
        if step is None:
            def step(at_blk, a_blk, x_blk, k):
                _, k_x = jax.random.split(k)
                x_t = crossbar._encode_vec(x_blk, k_x, cfg) \
                    if cfg.encode_inputs else x_blk
                from repro.kernels import ops as kops
                with jax.named_scope("meliso.tier1"):
                    if transpose:
                        if not cfg.ec:
                            return at_blk.T @ x_t
                        if use_kernel:
                            return kops.rram_ec_tile_rmvm(x_blk, x_t, at_blk,
                                                          a_blk - at_blk)
                        if cfg.ec_mode == "faithful":
                            return (at_blk.T @ x_blk + a_blk.T @ x_t
                                    - at_blk.T @ x_t)
                        return at_blk.T @ x_blk + (a_blk - at_blk).T @ x_t
                    if not cfg.ec:
                        return at_blk @ x_t
                    if use_kernel:
                        return kops.rram_ec_tile_mvm(x_blk, x_t, at_blk,
                                                     a_blk - at_blk)
                    if cfg.ec_mode == "faithful":
                        return at_blk @ x_blk + a_blk @ x_t - at_blk @ x_t
                    return at_blk @ x_blk + (a_blk - at_blk) @ x_t

            # Jitted once per engine (per direction/backend): execute-many
            # calls reuse the trace.
            step = jax.jit(step)
            self._streamed_step[(use_kernel, transpose)] = step
        out_blocks, acc_cap = (nb, cap_n) if transpose else (mb, cap_m)
        rows = []
        for o in range(out_blocks):
            acc = jnp.zeros((acc_cap, batch), jnp.float32)
            for c in range(mb if transpose else nb):
                i, j = (c, o) if transpose else (o, c)
                acc = acc + step(A.at_blocks[i, j], A.block_fn(i, j),
                                 x_chunks[c], keys[i, j])
            rows.append(acc)
        p = jnp.concatenate(rows, axis=0)[:n if transpose else m]
        if cfg.ec:
            p = denoise_least_square(p, lam=cfg.lam, h=cfg.h,
                                     method=cfg.denoise_method)
        return p
