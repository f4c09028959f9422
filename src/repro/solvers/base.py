"""Solver-layer plumbing: operators, results, and the energy/latency ledger.

MELISO+ is an in-memory *linear solver*: the regime that pays for programming
an RRAM image once is hundreds of matvecs against it (the companion PDHG paper
runs exactly this loop).  This module is the contract between the iterative
methods (:mod:`stationary`, :mod:`krylov`, :mod:`refinement`) and whatever
supplies the matvec:

  * :func:`as_operator` adapts an :class:`~repro.engine.AnalogMatrix` (noisy,
    error-corrected analog MVM + real write-cost accounting), a transposed
    :class:`~repro.engine.TransposedAnalogMatrix` view, a dense
    ``jnp.ndarray`` (exact digital matvec, zero analog cost -- the oracle used
    in tests), or a bare ``matvec(v, key)`` callable into one
    :class:`LinearOperator` interface.  Every solver is matvec-only -- plus
    ``rmatvec`` (the corrected TRANSPOSED MVM ``A.T @ u`` against the same
    programmed image) for the primal-dual methods -- so the same code runs
    unchanged against ``local``, ``streamed`` and ``distributed`` execution
    and both engine backends.
  * :class:`SolveResult` is what every solver returns: the solution, the
    per-iteration relative-residual history, convergence info, and a
    :class:`SolveLedger` splitting energy/latency into the one-time
    programming cost (``write_stats``, paid at ``engine.program``) and the
    per-iteration input-write cost (one x DAC pass + EC X^T replica per MVM).

Key discipline: each analog MVM inside a solve consumes ``fold_in(key, i)``
for a global matvec counter ``i``, so a solve is reproducible given its base
key and two solvers issued the same draws never correlate across iterations.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.write_verify import WriteStats
from repro.engine import AnalogMatrix, TransposedAnalogMatrix

__all__ = [
    "LinearOperator", "SolveLedger", "SolveResult", "as_operator",
    "col_norms", "init_history", "jit_core", "use_pallas", "solver_core",
    "dispatched", "SPAN_DISPATCH",
]

_TINY = 1e-30

#: The host span around each call of a jitted solver core; its arguments
#: ``tier1`` and ``mesh`` are those of :func:`dispatched`.
SPAN_DISPATCH = "meliso.solver.dispatch"


def use_pallas(backend: Optional[str]) -> bool:
    """Validate a solver ``backend=`` switch (None -> reference path)."""
    if backend is None:
        return False
    if backend not in ("reference", "pallas"):
        raise ValueError(f"unknown solver backend {backend!r}")
    return backend == "pallas"


def col_norms(v: jnp.ndarray) -> jnp.ndarray:
    """Column-wise l2 norms of an (n, batch) panel -> (batch,)."""
    return jnp.sqrt(jnp.sum(jnp.square(v), axis=0))


def init_history(maxiter: int, batch: int) -> jnp.ndarray:
    """NaN-filled (maxiter, batch) relative-residual history; iterations that
    never run stay NaN so plots/tests can distinguish 'converged early'."""
    return jnp.full((maxiter, batch), jnp.nan, jnp.float32)


@dataclasses.dataclass(frozen=True)
class LinearOperator:
    """Matvec-only view of a (square or rectangular) matrix.

    ``matvec(v, key)`` maps (n, batch) -> (m, batch); ``key`` seeds the input
    DAC noise of an analog execution and is ignored by digital operators.
    ``rmatvec(u, key)`` -- when available -- maps (m, batch) -> (n, batch)
    through the TRANSPOSED corrected MVM ``A.T @ u`` against the same
    programmed image (``None`` for operators that cannot transpose, e.g. a
    bare matvec callable without an explicit ``rmatvec=``); primal-dual
    methods (:func:`repro.solvers.pdhg`) require it.
    ``input_stats_t`` bills one transposed MVM's input writes (the m-length
    DAC pass + the row-dimension EC replica).
    """

    matvec: Callable[[jnp.ndarray, jax.Array], jnp.ndarray]
    shape: Tuple[int, int]
    write_stats: WriteStats                      # one-time programming cost
    input_stats: Callable[[int], WriteStats]     # per-MVM cost, fn of batch
    dense: Optional[Callable[[], jnp.ndarray]]   # digital reconstruction
    analog: bool
    rmatvec: Optional[Callable[[jnp.ndarray, jax.Array], jnp.ndarray]] = None
    input_stats_t: Optional[Callable[[int], WriteStats]] = None
    # The device arrays the matvecs read, and ``bind(operands)`` -> the same
    # operator reading the given ones: :func:`jit_core` passes them into a
    # solver's jitted core as arguments instead of capturing them as
    # compile-time constants (a resident image is gigabytes).
    operands: Any = ()
    bind: Optional[Callable[[Any], "LinearOperator"]] = None
    # For an engine-backed operator: the form of the tier-1 kernel its
    # matvec (``tier1``) and rmatvec (``tier1_t``) run for a given column
    # count (``AnalogEngine.tier1_form``; None where no kernel runs).
    tier1: Optional[Callable[[int], Optional[str]]] = None
    tier1_t: Optional[Callable[[int], Optional[str]]] = None
    # The device mesh the matvecs span (``AnalogEngine.mesh_label``, "2x2"),
    # None on one device.
    mesh: Optional[str] = None

    @property
    def n(self) -> int:
        return self.shape[1]

    @property
    def T(self) -> "LinearOperator":
        """The transposed operator (matvec/rmatvec and shapes swapped).

        Requires ``rmatvec``; shares the parent's write_stats (the programmed
        image is one physical object, whichever direction it is read)."""
        if self.rmatvec is None:
            raise ValueError("operator has no rmatvec; cannot transpose")
        return LinearOperator(
            matvec=self.rmatvec, rmatvec=self.matvec,
            shape=(self.shape[1], self.shape[0]),
            write_stats=self.write_stats,
            input_stats=self.input_stats_t or self.input_stats,
            input_stats_t=self.input_stats,
            dense=(lambda: self.dense().T) if self.dense is not None else None,
            analog=self.analog,
            operands=self.operands,
            bind=(lambda ops: self.bind(ops).T) if self.bind else None,
            tier1=self.tier1_t,
            tier1_t=self.tier1,
            mesh=self.mesh,
        )


def solver_core(core: Callable) -> Callable:
    """Decorator of a solver core: its operations are named ``meliso.solver``
    in traces.  The matvecs it calls keep their own stage names (the
    innermost scope names an operation)."""
    @functools.wraps(core)
    def scoped(*args, **kwargs):
        with jax.named_scope("meliso.solver"):
            return core(*args, **kwargs)
    return scoped


def _panel_cols(args) -> int:
    """Columns of the first (n, batch) panel among a core's arguments (its
    right-hand side); a core that takes none iterates one vector."""
    return next((a.shape[1] for a in args if getattr(a, "ndim", 0) == 2), 1)


def dispatched(core: Callable, op: LinearOperator) -> Callable:
    """``core`` with each call inside the host span :data:`SPAN_DISPATCH`
    (which records nothing unless the profiler is tracing).  Where the
    operator ``op`` runs a tier-1 kernel, :attr:`LinearOperator.tier1` gives
    the span's ``tier1`` argument from the right-hand side's columns: the
    form its matvecs take; where its matvecs span a mesh, the span's
    ``mesh`` argument is :attr:`LinearOperator.mesh`."""
    mesh = {} if op.mesh is None else {"mesh": op.mesh}

    def dispatch(*args):
        form = op.tier1(_panel_cols(args)) if op.tier1 is not None else None
        with jax.profiler.TraceAnnotation(
                SPAN_DISPATCH, **mesh,
                **({} if form is None else {"tier1": form})):
            return core(*args)
    return dispatch


def jit_core(op: LinearOperator, build: Callable[[LinearOperator], Callable]
             ) -> Callable:
    """``jax.jit(build(op))`` with ``op.operands`` passed as arguments, each
    call :func:`dispatched`.

    A jitted function captures the arrays it closes over as constants of the
    compiled program: a second device copy, and host copies while it
    compiles.  Binding the operator to the jit's own arguments keeps a
    programmed image a single buffer however large it is."""
    if op.bind is None:
        return dispatched(jax.jit(build(op)), op)
    core = jax.jit(lambda operands, *args: build(op.bind(operands))(*args))
    return dispatched(functools.partial(core, op.operands), op)


def _zero_stats(_batch: int = 1) -> WriteStats:
    return WriteStats.zero()


def as_operator(
    A: Union[AnalogMatrix, jnp.ndarray, Callable],
    *,
    shape: Optional[Tuple[int, int]] = None,
    rmatvec: Optional[Callable] = None,
) -> LinearOperator:
    """Adapt ``A`` into a :class:`LinearOperator`.

    ``A`` may be an :class:`AnalogMatrix` handle (programmed once; each matvec
    is a corrected analog execution whose input-write cost lands in the
    ledger -- and ``rmatvec`` is its corrected TRANSPOSED execution against
    the same image), a :class:`~repro.engine.TransposedAnalogMatrix` view
    (``A.T``: matvec/rmatvec swapped), a dense array (exact digital matvec +
    rmatvec, zero ledger), or a callable ``matvec(v, key)`` with
    ``shape=(m, n)`` (optionally ``rmatvec=`` for methods that need
    ``A.T @ u``).
    """
    if isinstance(A, LinearOperator):
        return A
    if isinstance(A, TransposedAnalogMatrix):
        return as_operator(A.parent).T
    if isinstance(A, AnalogMatrix):
        # Streamed handles with a traceable producer keep the whole solve one
        # compiled program: each matvec inside the solver's jitted core traces
        # the engine's scan-fused pipeline inline (one dispatch per MVM), and
        # ``dense()`` reconstructs A with a single producer sweep (used by
        # jacobi's diagonal and refine's digital outer residual).
        # Distributed handles stay distributed: the matvec's output is
        # row-sharded straight out of shard_map, and because the solver
        # reductions are plain per-column jnp ops, GSPMD keeps the x/r/p
        # panels sharded across the whole jitted while_loop -- no gathers.
        eng = A.engine
        fields = ("at_blocks", "da_blocks", "at_dense", "da_dense", "age")
        return LinearOperator(
            matvec=lambda v, k: eng.mvm(A, v, key=k),
            rmatvec=lambda u, k: eng.rmvm(A, u, key=k),
            shape=A.shape,
            write_stats=A.write_stats,
            input_stats=lambda batch: eng.input_write_stats(A, batch),
            input_stats_t=lambda batch: eng.input_write_stats(
                A, batch, transpose=True),
            dense=A.dense,
            analog=True,
            operands={f: getattr(A, f) for f in fields
                      if getattr(A, f) is not None},
            bind=lambda ops: as_operator(dataclasses.replace(A, **ops)),
            tier1=eng.tier1_form,
            tier1_t=functools.partial(eng.tier1_form, transpose=True),
            mesh=eng.mesh_label,
        )
    if callable(A) and not hasattr(A, "shape"):
        if shape is None:
            raise ValueError("as_operator(matvec, ...) requires shape=(m, n)")
        return LinearOperator(matvec=A, rmatvec=rmatvec, shape=tuple(shape),
                              write_stats=WriteStats.zero(),
                              input_stats=_zero_stats, dense=None,
                              analog=False)
    a = jnp.asarray(A)
    if a.ndim != 2:
        raise ValueError(f"expected a matrix, got shape {a.shape}")
    return LinearOperator(matvec=lambda v, _k: a @ v,
                          rmatvec=lambda u, _k: a.T @ u,
                          shape=a.shape,
                          write_stats=WriteStats.zero(),
                          input_stats=_zero_stats, dense=lambda: a,
                          analog=False, operands=a, bind=as_operator)


@dataclasses.dataclass(frozen=True)
class SolveLedger:
    """Energy/latency split of one solve under the program-once model.

    ``write_stats`` is the one-time conductance-image programming cost (zero
    for digital operators); ``input_stats`` is the cost of ONE analog MVM's
    input writes (x DAC pass + EC X^T replica, scaling with the RHS batch);
    ``mvms`` counts the full-batch analog MVMs the solve executed.  Setup
    MVMs that run on a single column regardless of the RHS batch (the
    power-iteration spectral estimate) are billed separately as
    ``mvms_single`` at the ``input_stats_single`` (batch=1) rate, so the
    amortized totals are ``write + mvms*input + mvms_single*input_single``.
    Primal-dual solves additionally execute TRANSPOSED MVMs against the same
    image: those are counted in ``mvms_t`` at the ``input_stats_t`` rate
    (the m-length y DAC pass + the row-dimension EC replica), and their
    batch-1 setup half (the power-iteration steps on ``A.T A`` alternate one
    forward with one transposed MVM) in ``mvms_single_t`` at the batch-1
    transposed rate -- the matrix write is still paid exactly once,
    whichever directions read it.
    """

    write_stats: WriteStats
    input_stats: WriteStats
    mvms: int
    input_stats_single: Optional[WriteStats] = None
    mvms_single: int = 0
    input_stats_t: Optional[WriteStats] = None
    mvms_t: int = 0
    input_stats_single_t: Optional[WriteStats] = None
    mvms_single_t: int = 0

    @property
    def write_energy_j(self) -> float:
        return float(self.write_stats.energy_j)

    def _rates(self):
        single = self.input_stats_single or self.input_stats
        transposed = self.input_stats_t or self.input_stats
        single_t = self.input_stats_single_t or transposed
        return ((self.input_stats, self.mvms), (single, self.mvms_single),
                (transposed, self.mvms_t), (single_t, self.mvms_single_t))

    @property
    def iteration_energy_j(self) -> float:
        return sum(float(rate.energy_j) * count
                   for rate, count in self._rates())

    @property
    def total_energy_j(self) -> float:
        return self.write_energy_j + self.iteration_energy_j

    @property
    def total_latency_s(self) -> float:
        return float(self.write_stats.latency_s) + sum(
            float(rate.latency_s) * count for rate, count in self._rates())


@dataclasses.dataclass
class SolveResult:
    """What every solver in :mod:`repro.solvers` returns.

    ``residuals`` is the per-iteration relative residual ``||r_k|| / ||b||``,
    shaped (maxiter,) for a vector RHS or (maxiter, batch) for multi-RHS;
    entries past ``iterations`` are NaN.  For restarted GMRES one "iteration"
    is one restart cycle.  ``initial_residual`` is the worst-column relative
    residual at ENTRY (after the init MVM, before any update): a solve that
    is already converged there stops at ``iterations == 0`` with an all-NaN
    history, and ``final_residual``/``converged`` report the entry residual
    instead of the old dishonest ``-inf`` / ``False``.  Solvers without an
    init MVM (the stationary methods always run >= 1 iteration) leave it NaN.
    """

    x: jnp.ndarray
    residuals: jnp.ndarray
    iterations: int
    converged: bool
    ledger: SolveLedger
    solver: str
    initial_residual: float = float("nan")
    # Primal-dual solves (pdhg) also return the dual variable y; None for
    # the purely-primal linear-system solvers.
    dual: Optional[jnp.ndarray] = None
    # Checkpoint restores a fault-tolerant wrapper performed to finish this
    # solve (repro.reliability.ft_solve); 0 for a clean run.
    restores: int = 0
    # Eigen-solves (lanczos / lobpcg) return their eigenvalue estimates here
    # (ascending, matching the columns of x); None for linear solves.
    eigenvalues: Optional[jnp.ndarray] = None

    @property
    def final_residual(self) -> float:
        """Worst-column relative residual at the last recorded iteration (the
        entry residual when the solve converged before iterating)."""
        if self.iterations == 0:
            return self.initial_residual
        r = self.residuals if self.residuals.ndim == 2 \
            else self.residuals[:, None]
        row = r[self.iterations - 1]
        if bool(jnp.all(jnp.isnan(row))):
            # Breakdown (e.g. a device fault mid-solve): the recorded row is
            # all NaN.  Report NaN -- which compares False against any tol --
            # instead of the old -inf, which read as "converged".
            return float("nan")
        return float(jnp.nanmax(row))

    def __repr__(self) -> str:  # keep large arrays out of logs
        m, b = (self.residuals.shape + (1,))[:2]
        return (f"SolveResult(solver={self.solver!r}, n={self.x.shape[0]}, "
                f"batch={b}, iterations={self.iterations}, "
                f"converged={self.converged}, "
                f"final_residual={self.final_residual:.3e}, "
                f"mvms={self.ledger.mvms}, "
                f"energy_j={self.ledger.total_energy_j:.3e})")


def pack_result(
    op: LinearOperator,
    solver: str,
    x: jnp.ndarray,
    hist: jnp.ndarray,
    iterations,
    mvms,
    tol: float,
    squeeze: bool,
    mvms_single: int = 0,
    rel0=None,
    mvms_t: int = 0,
    mvms_single_t: int = 0,
) -> SolveResult:
    """Assemble a :class:`SolveResult` from a jitted core's raw outputs.

    ``mvms`` are full-batch solve MVMs; ``mvms_single`` are batch-1 setup
    MVMs (spectral estimates), billed at the batch-1 input-write rate;
    ``mvms_t`` / ``mvms_single_t`` are the full-batch / batch-1 TRANSPOSED
    counterparts, billed at the transposed rates.  ``rel0`` is the per-column relative
    residual at entry (from the core's init MVM), which makes iteration-0
    convergence honest: zero RHS or an exact ``x0`` yields
    ``converged=True`` with ``final_residual == rel0`` rather than
    ``False`` / ``-inf``.
    """
    batch = x.shape[1]
    iterations = int(iterations)
    initial = float(jnp.max(rel0)) if rel0 is not None else float("nan")
    stats_t = op.input_stats_t or op.input_stats
    res = SolveResult(
        x=x[:, 0] if squeeze else x,
        residuals=hist[:, 0] if squeeze else hist,
        iterations=iterations,
        converged=False,
        ledger=SolveLedger(write_stats=op.write_stats,
                           input_stats=op.input_stats(batch),
                           mvms=int(mvms),
                           input_stats_single=op.input_stats(1),
                           mvms_single=int(mvms_single),
                           input_stats_t=stats_t(batch),
                           mvms_t=int(mvms_t),
                           input_stats_single_t=stats_t(1),
                           mvms_single_t=int(mvms_single_t)),
        solver=solver,
        initial_residual=initial,
    )
    # NaN-robust: a NaN final residual (breakdown, or iteration 0 with no
    # recorded entry residual) compares False and stays not-converged.
    res.converged = bool(res.final_residual <= tol)
    return res
