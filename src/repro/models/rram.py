"""Programming a model's linear layers onto the RRAM analog backend.

``program_rram`` is a pytree walk of :meth:`repro.engine.AnalogEngine.program`:
every 2-D linear kernel named "w" is programmed once onto the engine and gains
two siblings extracted from the resulting :class:`~repro.engine.AnalogMatrix`:

  * ``w_tilde``: the encoded (quantized + programming-noise) conductance image,
    produced by per-(cell_rows x cell_cols)-tile encoding after ``k_iters``
    write-verify passes -- exactly :func:`repro.core.crossbar.encode_tiled`.
  * ``dw = w - w_tilde``: the tier-1 correction operand (stored in
    ``dw_dtype``; bf16 by default -- dw is O(sigma * w), so the beyond-paper
    compression costs ~sigma * 2^-8 relative error, measured in tests).

It also returns the aggregate :class:`WriteStats` for programming the whole
model -- the analog deployment's one-time write energy/latency (matrix writes
only: per-token input-DAC cost is an execution-time figure under the
program-once accounting), reported by the serve benchmarks.  ``program_specs``
is the shape-level twin used by the dry-run (no allocation).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import RRAMBackendConfig
from repro.core.crossbar import CrossbarConfig, input_write_cost, \
    matrix_write_cost
from repro.core.devices import get_device
from repro.core.virtualization import MCAGeometry
from repro.core.write_verify import WriteStats
from repro.engine import AnalogEngine
from .params import is_spec, spec

__all__ = ["program_rram", "program_specs", "programming_dispatch_plan",
           "crossbar_cfg", "is_programmed", "strip_rram", "reprogram_rram",
           "analog_image_bytes", "programmed_kernel_shapes",
           "forward_input_stats"]


def crossbar_cfg(cfg: RRAMBackendConfig) -> CrossbarConfig:
    return CrossbarConfig(
        device=get_device(cfg.device),
        geom=MCAGeometry(tile_rows=1, tile_cols=1,
                         cell_rows=cfg.cell_rows, cell_cols=cfg.cell_cols),
        k_iters=cfg.k_iters, ec=cfg.ec, ec_mode=cfg.ec_mode,
        denoise_method=cfg.denoise_method, lam=cfg.lam,
        encode_inputs=cfg.encode_inputs,
    )


def program_rram(
    params: Any,
    cfg: RRAMBackendConfig,
    key: jax.Array,
    *,
    engine: Optional[AnalogEngine] = None,
    group: bool = True,
) -> Tuple[Any, WriteStats]:
    """Return (programmed params, total write stats).

    A pytree walk of the engine's programming stage: each kernel is written
    onto the analog engine exactly once; the dense ``w_tilde``/``dw``
    operands the layers consume are views of the programmed image.  Works on
    real or stacked (scan-over-layers) kernels: a kernel of shape
    (L, d_in, d_out) is encoded per layer (each layer maps onto its own set
    of MCA tiles).

    ``group=True`` (the default) programs all same-shape kernels of the walk
    as ONE grouped dispatch each (the :class:`~repro.engine.AnalogMatrixGroup`
    batching applied to programming): a whole model writes in
    O(distinct kernel shapes) device launches instead of O(kernels).  Each
    kernel keeps the exact per-kernel key of the ungrouped walk (fold
    ``counter`` of ``key``), so every draw is the same random variate under
    either setting; images agree to float32 rounding (~1e-7 -- XLA may
    reassociate the fused encode differently than the eager per-kernel
    path), and the dispatch count drops from O(kernels) to
    O(distinct shapes) (see :func:`programming_dispatch_plan`).
    """
    engine = engine or AnalogEngine(crossbar_cfg(cfg))
    ccfg = engine.cfg
    total = WriteStats.zero()
    counter = [0]
    jobs = []       # (slot dict, kernel, per-kernel key) in walk order

    def visit(tree):
        if not isinstance(tree, dict):
            return tree
        out = {}
        for name, sub in tree.items():
            if name == "w" and hasattr(sub, "ndim") and sub.ndim in (2, 3):
                counter[0] += 1
                k = jax.random.fold_in(key, counter[0])
                out[name] = sub
                out["w_tilde"] = None
                out["dw"] = None
                jobs.append((out, sub, k))
            elif isinstance(sub, dict):
                out[name] = visit(sub)
            else:
                out[name] = sub
        return out

    tree = visit(params)

    def per_layer_stats(m, n, layers):
        per = matrix_write_cost(m, n, ccfg)
        return WriteStats(
            energy_j=per.energy_j * layers, latency_s=per.latency_s * layers,
            iterations=per.iterations, final_delta=per.final_delta)

    def fill(slot, sub, wt):
        slot["w_tilde"] = wt.astype(sub.dtype)
        slot["dw"] = (sub.astype(jnp.float32) - wt).astype(cfg.dw_dtype)

    if not group:
        for slot, sub, k in jobs:
            if sub.ndim == 2:
                handle = engine.program(sub.astype(jnp.float32), k)
                wt = handle.a_tilde
                total = total + handle.write_stats
            else:
                keys = jax.random.split(k, sub.shape[0])
                wt = jax.vmap(engine.encode_dense)(
                    sub.astype(jnp.float32), keys)
                total = total + per_layer_stats(sub.shape[1], sub.shape[2],
                                                sub.shape[0])
            fill(slot, sub, wt)
        return tree, total

    # Grouped programming: bucket the walk by (ndim, shape) and encode each
    # bucket's kernels in one dispatch.  Stacked (L, m, n) kernels
    # keep their per-layer split keys, 2-D kernels their per-kernel fold --
    # member draws match the ungrouped walk exactly.
    buckets: Dict[Tuple, list] = {}
    for job in jobs:
        sub = job[1]
        buckets.setdefault((sub.ndim,) + tuple(sub.shape), []).append(job)

    def encode(w, k):
        """One (m, n) kernel -> (w_tilde, dw) in their storage dtypes."""
        wt = engine.encode_dense(w.astype(jnp.float32), k)
        return wt.astype(w.dtype), \
            (w.astype(jnp.float32) - wt).astype(cfg.dw_dtype)

    def encode_job(w, k):
        if w.ndim == 2:
            return encode(w, k)
        keys = jax.random.split(k, w.shape[0])
        return jax.lax.map(lambda o: encode(*o), (w, keys))

    # One dispatch per bucket.  Kernels and layers are encoded one after
    # another and written straight to their own outputs, so programming
    # holds O(one kernel) of float32 transients and no stacked copies.
    encode_all = jax.jit(lambda ws, ks: [encode_job(w, k)
                                         for w, k in zip(ws, ks)])
    for bkey, bjobs in buckets.items():   # insertion order == walk order
        m, n = bkey[-2:]
        layers = bkey[1] if bkey[0] == 3 else 1
        total = total + per_layer_stats(m, n, len(bjobs) * layers)
        outs = encode_all([j[1] for j in bjobs], [j[2] for j in bjobs])
        for (slot, _, _), (wt, dw) in zip(bjobs, outs):
            slot["w_tilde"], slot["dw"] = wt, dw
    return tree, total


def programming_dispatch_plan(params: Any) -> Dict[str, int]:
    """Dispatch accounting of one :func:`program_rram` walk over ``params``.

    ``kernels`` is how many programmed kernels the walk visits (the ungrouped
    dispatch count); ``groups`` how many distinct (ndim, shape) buckets they
    collapse into (the grouped dispatch count).  Pure shape math -- works on
    programmed or digital trees."""
    shapes = []

    def visit(tree):
        if isinstance(tree, dict):
            for name, sub in tree.items():
                if name == "w" and hasattr(sub, "ndim") and \
                        sub.ndim in (2, 3):
                    shapes.append((sub.ndim,) + tuple(sub.shape))
                elif isinstance(sub, dict):
                    visit(sub)

    visit(params)
    return {"kernels": len(shapes), "groups": len(set(shapes))}


def is_programmed(params: Any) -> bool:
    """True iff the pytree already carries analog images (``w_tilde``)."""
    found = [False]

    def visit(tree):
        if isinstance(tree, dict):
            if "w_tilde" in tree:
                found[0] = True
            for sub in tree.values():
                visit(sub)

    visit(params)
    return found[0]


def strip_rram(params: Any) -> Any:
    """Drop every ``w_tilde``/``dw`` sibling, returning digital-only params."""

    def visit(tree):
        if not isinstance(tree, dict):
            return tree
        return {name: visit(sub) for name, sub in tree.items()
                if name not in ("w_tilde", "dw")}

    return visit(params)


def reprogram_rram(
    params: Any,
    cfg: RRAMBackendConfig,
    key: jax.Array,
    *,
    engine: Optional[AnalogEngine] = None,
) -> Tuple[Any, WriteStats]:
    """Program a (possibly already-programmed) pytree under a fresh key.

    The per-tenant entry point for the serving image cache: the same digital
    weights programmed under two different keys produce independent device
    draws (independent ``w_tilde`` noise), and every reprogram is billed the
    full one-time matrix :class:`WriteStats` again -- this is the cost a
    write-cost-aware eviction policy is trying not to pay twice."""
    return program_rram(strip_rram(params), cfg, key, engine=engine)


def analog_image_bytes(params: Any) -> int:
    """Resident bytes of the programmed analog operands (w_tilde + dw).

    The serving cache's capacity accounting: what it costs to *keep* a
    tenant's image programmed, as opposed to the :class:`WriteStats` energy
    it costs to *create* it."""
    total = [0]

    def visit(tree):
        if isinstance(tree, dict):
            for name, sub in tree.items():
                if name in ("w_tilde", "dw") and hasattr(sub, "nbytes"):
                    total[0] += int(sub.nbytes)
                else:
                    visit(sub)

    visit(params)
    return total[0]


def programmed_kernel_shapes(params: Any) -> Tuple[Tuple[int, int, int], ...]:
    """(layers, d_in, d_out) of every programmed kernel (layers=1 if 2-D)."""
    out = []

    def visit(tree):
        if isinstance(tree, dict):
            for name, sub in tree.items():
                if name == "w_tilde" and hasattr(sub, "ndim"):
                    if sub.ndim == 2:
                        out.append((1, sub.shape[0], sub.shape[1]))
                    else:
                        out.append(tuple(int(d) for d in sub.shape))
                else:
                    visit(sub)

    visit(params)
    return tuple(out)


def forward_input_stats(params: Any, cfg: RRAMBackendConfig,
                        batch: int = 1) -> WriteStats:
    """Per-forward-pass input-DAC cost through every programmed kernel.

    One token position through ``dense(x, w)`` is one corrected MVM against
    the analog operator A = w^T of shape (d_out, d_in); a forward pass with
    ``batch`` positions therefore pays ``input_write_cost(d_out, d_in,
    batch=batch)`` per layer.  This is the per-MVM side of the
    ``SolveLedger`` split -- the marginal energy/latency of one decode step
    (``batch=B``) or one prefill (``batch=B*T``) once the image is resident.
    """
    ccfg = crossbar_cfg(cfg)
    total = WriteStats.zero()
    for layers, d_in, d_out in programmed_kernel_shapes(params):
        per = input_write_cost(d_out, d_in, ccfg, batch=batch)
        total = total + WriteStats(
            energy_j=per.energy_j * layers, latency_s=per.latency_s * layers,
            iterations=per.iterations, final_delta=per.final_delta)
    return total


def program_specs(specs: Any, cfg: RRAMBackendConfig) -> Any:
    """Spec-tree twin of :func:`program_rram` for dry-runs: adds w_tilde/dw
    ParamSpecs with the same shapes/logical axes as each kernel."""

    def visit(tree):
        if not isinstance(tree, dict):
            return tree
        out = {}
        for name, sub in tree.items():
            if name == "w" and is_spec(sub) and len(sub.shape) in (2, 3):
                out[name] = sub
                out["w_tilde"] = spec(sub.shape, sub.axes, init="zeros",
                                      dtype=sub.dtype)
                out["dw"] = spec(sub.shape, sub.axes, init="zeros",
                                 dtype=cfg.dw_dtype)
            elif isinstance(sub, dict):
                out[name] = visit(sub)
            else:
                out[name] = sub
        return out

    return visit(specs)
