"""Jaxpr invariant verifier: static proofs over traced pipelines.

The paper's headline claims are *structural* properties of the compiled
program, visible in its jaxpr before anything runs:

* a >= 65,536^2 solve never holds an A-sized array (**AvalBound**);
* a streamed solve is a single device dispatch and re-invokes the block
  producer a bounded number of times (**DispatchCount**);
* every PRNG consumption is reachable from a distinct fold of the root
  key, so the k_a/k_x block-key schedule is provably collision-free and
  draw-identity across placements holds (**KeyReuse**);
* no silent float64 leaks and no sub-f32 accumulators in scan carries or
  collective operands (**PrecisionLint**);
* inside ``shard_map`` the only cross-device reductions are psums over
  the declared row/col axes, and no all-gather/all-to-all ships more
  than a per-device block (**CollectiveAudit**).

This module provides the one shared IR walker (:func:`walk_frames` /
:func:`iter_equations`) -- recursing into scan/while/cond/jit/shard_map
and ``custom_vjp`` sub-jaxprs, including jaxprs reached through dict or
nested-container params and the ``fwd_jaxpr_thunk`` callable -- plus the
five passes.  Each violation carries a :class:`Site` naming the
offending primitive, its path through the IR, and the user source line.

``analysis.memory`` re-exports its ``jaxpr_max_elements`` on top of this
walker so there is exactly one traversal implementation.

The canonical pipeline matrix lives in :mod:`repro.analysis.pipelines`;
``tools/check_invariants.py`` runs every pass over every registered
pipeline against the checked-in ``INVARIANTS.json`` manifest.

See DESIGN.md section 10 (static invariants) and DESIGN.md section 4
(key discipline the KeyReuse pass enforces).
"""
from __future__ import annotations

import dataclasses
import hashlib
import sys
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import numpy as np

from jax.extend.core import ClosedJaxpr, Jaxpr, Literal

__all__ = [
    "Site",
    "Violation",
    "Report",
    "CallCounter",
    "trace",
    "walk_frames",
    "iter_equations",
    "eqn_subjaxprs",
    "jaxpr_max_elements",
    "aval_bound",
    "dispatch_count",
    "key_reuse",
    "precision_lint",
    "collective_audit",
    "run_all",
]

# Primitives that open a new trace/dispatch scope when they appear at the
# top level of an un-jitted trace.
DISPATCH_PRIMITIVES = frozenset({
    "jit", "scan", "while", "cond", "shard_map", "remat2",
    "custom_jvp_call", "custom_vjp_call",
})

# PRNG primitives.  ``random_bits`` is the consumption point jax traces
# `jax.random.*` draws into; raw threefry shows up only in lowered paths but
# is handled for completeness.
RANDOM_CONSUMERS = frozenset({"random_bits", "threefry2x32"})

# ``psum_invariant`` is the psum a shard_map with replication (vma) checking
# traces; ``psum`` the one without.
COLLECTIVE_REDUCERS = frozenset({"psum", "psum_invariant"})
COLLECTIVE_GATHERS = frozenset({"all_gather", "all_to_all"})

_SUB_JAXPR_DEPTH = 6  # containers nested deeper than this are not scanned


# --------------------------------------------------------------------------
# attribution
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Site:
    """Where a violation lives: primitive, IR path, and user source line."""

    primitive: str
    path: Tuple[str, ...] = ()
    file: Optional[str] = None
    line: Optional[int] = None
    function: Optional[str] = None

    def __str__(self) -> str:
        loc = "/".join((*self.path, self.primitive)) or self.primitive
        if self.file is not None:
            src = self.file.rsplit("/", 1)[-1]
            loc += f" @ {src}:{self.line}"
            if self.function:
                loc += f" (in {self.function})"
        return loc


def _eqn_site(eqn: Any, path: Tuple[str, ...]) -> Site:
    name = getattr(getattr(eqn, "primitive", None), "name", "<jaxpr>")
    file = line = function = None
    try:  # private, best-effort: violations still render without it
        from jax._src import source_info_util

        frame = source_info_util.user_frame(eqn.source_info.traceback)
        if frame is not None:
            file = frame.file_name
            line = frame.start_line
            function = frame.function_name.rsplit(".", 1)[-1]  # qualname
    except Exception:  # pragma: no cover - jax internals moved
        pass
    return Site(name, path, file, line, function)


@dataclasses.dataclass(frozen=True)
class Violation:
    pass_name: str
    message: str
    site: Optional[Site] = None

    def __str__(self) -> str:
        tail = f" [{self.site}]" if self.site is not None else ""
        return f"{self.pass_name}: {self.message}{tail}"


@dataclasses.dataclass
class Report:
    """Result of one pass: summary metrics plus any violations."""

    pass_name: str
    violations: List[Violation] = dataclasses.field(default_factory=list)
    summary: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def ok(self) -> bool:
        return not self.violations

    def assert_ok(self) -> "Report":
        if self.violations:
            lines = "\n  ".join(str(v) for v in self.violations)
            raise AssertionError(f"{self.pass_name} failed:\n  {lines}")
        return self

    def __str__(self) -> str:
        status = "ok" if self.ok else f"{len(self.violations)} violation(s)"
        return f"[{self.pass_name}] {status} {self.summary}"


# --------------------------------------------------------------------------
# the shared walker
# --------------------------------------------------------------------------

def _as_jaxpr(jaxpr: Any) -> Jaxpr:
    return jaxpr.jaxpr if isinstance(jaxpr, ClosedJaxpr) else jaxpr


def _jaxprs_in(value: Any, depth: int = 0) -> Iterator[Jaxpr]:
    """Every jaxpr reachable inside an eqn param value.

    Handles raw ``Jaxpr``/``ClosedJaxpr`` as well as tuples, lists and
    dicts nested up to ``_SUB_JAXPR_DEPTH`` levels -- the seed walker
    only looked one container level deep and missed e.g. dict-valued
    params (see tests/test_verify.py::TestWalkerRegressions).
    """
    if isinstance(value, Jaxpr):
        yield value
    elif isinstance(value, ClosedJaxpr):
        yield value.jaxpr
    elif depth < _SUB_JAXPR_DEPTH:
        if isinstance(value, dict):
            for item in value.values():
                yield from _jaxprs_in(item, depth + 1)
        elif isinstance(value, (tuple, list)):
            for item in value:
                yield from _jaxprs_in(item, depth + 1)


def _custom_vjp_fwd_jaxpr(eqn: Any) -> Optional[Jaxpr]:
    """Materialize the fwd jaxpr hidden behind ``fwd_jaxpr_thunk``.

    In a primal-only trace of a ``jax.custom_vjp`` function the forward
    rule (and any residual it allocates) is reachable *only* through
    this memoized thunk -- params-level container scanning cannot see
    it.  The thunk takes one symbolic-zero flag per primal input.
    """
    thunk = eqn.params.get("fwd_jaxpr_thunk")
    fun = eqn.params.get("call_jaxpr")
    if thunk is None or not isinstance(fun, ClosedJaxpr):
        return None
    n = len(fun.jaxpr.invars) - int(eqn.params.get("num_consts", 0) or 0)
    try:
        out = thunk.call_wrapped(*([False] * max(n, 0)))
    except Exception:
        return None
    if isinstance(out, tuple) and out and isinstance(out[0], (Jaxpr, ClosedJaxpr)):
        return _as_jaxpr(out[0])
    return None


def eqn_subjaxprs(eqn: Any) -> List[Tuple[str, Jaxpr]]:
    """(label, jaxpr) for every sub-jaxpr an equation can reach."""
    out: List[Tuple[str, Jaxpr]] = []
    seen: set = set()
    for sub in _jaxprs_in(eqn.params):
        if id(sub) not in seen:
            seen.add(id(sub))
            out.append((eqn.primitive.name, sub))
    if eqn.primitive.name == "custom_vjp_call":
        fwd = _custom_vjp_fwd_jaxpr(eqn)
        if fwd is not None and id(fwd) not in seen:
            out.append((f"{eqn.primitive.name}.fwd", fwd))
    return out


class Frame:
    """One jaxpr scope in a walked trace, with bindings to its parent.

    ``defs`` maps each var to the equation producing it inside this
    frame.  ``bindings`` maps frame invars either to the parent operand
    (``("var", parent, outer_var)``) or to an opaque root such as a scan
    carry (``("loop", label, index)``), a trace constant
    (``("const", index)``) or a top-level argument (``("arg", index)``).
    """

    __slots__ = ("jaxpr", "parent", "path", "bindings", "defs",
                 "shard_axes", "carries", "origin_site", "uid")

    def __init__(self, jaxpr: Jaxpr, parent: Optional["Frame"], path: Tuple[str, ...],
                 bindings: Dict[Any, Tuple], shard_axes: Optional[frozenset],
                 carries: Sequence[Any], origin_site: Optional[Site], uid: int):
        self.jaxpr = jaxpr
        self.parent = parent
        self.path = path
        self.bindings = bindings
        self.defs = {v: eqn for eqn in jaxpr.eqns for v in eqn.outvars}
        self.shard_axes = shard_axes
        self.carries = tuple(carries)
        self.origin_site = origin_site
        self.uid = uid


def _child_bindings(eqn: Any, sub: Jaxpr, parent: Frame,
                    scope: str) -> Tuple[Dict[Any, Tuple], Sequence[Any]]:
    """Bind ``sub.invars`` to the parent equation's operands.

    Returns (bindings, carry_vars).  Operand binding is exact for the
    structured control-flow primitives; unknown primitives fall back to
    positional binding when arities match and opaque roots otherwise.
    ``scope`` is unique per (equation, sub-jaxpr), so opaque roots of
    two sibling loops never unify -- they can only *hide* reuse across
    an unknown boundary, never fabricate it.
    """
    name = eqn.primitive.name
    invars = list(sub.invars)
    bindings: Dict[Any, Tuple] = {}
    carries: List[Any] = []

    def bind_positional(sub_vars: Sequence[Any], operands: Sequence[Any]) -> None:
        for sv, ov in zip(sub_vars, operands):
            bindings[sv] = ("var", parent, ov)

    if name == "scan":
        nc = eqn.params.get("num_consts", 0)
        nk = eqn.params.get("num_carry", 0)
        bind_positional(invars[:nc], eqn.invars[:nc])
        for i, sv in enumerate(invars[nc:nc + nk]):
            bindings[sv] = ("loop", scope, i)
            carries.append(sv)
        for i, sv in enumerate(invars[nc + nk:]):
            bindings[sv] = ("loop_x", scope, i)
    elif name == "while":
        cn = eqn.params.get("cond_nconsts", 0)
        bn = eqn.params.get("body_nconsts", 0)
        body = _as_jaxpr(eqn.params.get("body_jaxpr"))
        is_body = sub is body
        nconsts = bn if is_body else cn
        lo = cn if is_body else 0
        bind_positional(invars[:nconsts], eqn.invars[lo:lo + nconsts])
        for i, sv in enumerate(invars[nconsts:]):
            # cond and body see the same carry: share the scope token so
            # a key threaded through `while` unifies across both views
            bindings[sv] = ("loop", scope.rsplit("#", 1)[0], i)
            if is_body:
                carries.append(sv)
    elif name in ("cond", "switch"):
        bind_positional(invars, eqn.invars[1:])
    elif len(invars) == len(eqn.invars):
        bind_positional(invars, eqn.invars)
    else:
        for i, sv in enumerate(invars):
            bindings[sv] = ("opaque", scope, i)
    return bindings, carries


def walk_frames(jaxpr: Any) -> Iterator[Frame]:
    """Yield a :class:`Frame` for the jaxpr and every reachable sub-jaxpr."""
    jaxpr = _as_jaxpr(jaxpr)
    uid = [0]
    eqn_uid = [0]
    root_bindings: Dict[Any, Tuple] = {}
    for i, v in enumerate(jaxpr.invars):
        root_bindings[v] = ("arg", "", i)
    for i, v in enumerate(jaxpr.constvars):
        root_bindings[v] = ("const", "", i)
    root = Frame(jaxpr, None, (), root_bindings, None, (), None, uid[0])
    stack = [root]
    while stack:
        frame = stack.pop()
        yield frame
        for eqn in frame.jaxpr.eqns:
            eqn_uid[0] += 1
            for sub_idx, (label, sub) in enumerate(eqn_subjaxprs(eqn)):
                uid[0] += 1
                path = (*frame.path, label)
                scope = f"{eqn_uid[0]}#{sub_idx}"
                bindings, carries = _child_bindings(eqn, sub, frame, scope)
                for i, cv in enumerate(sub.constvars):
                    bindings[cv] = ("const", scope, i)
                shard_axes = frame.shard_axes
                if eqn.primitive.name == "shard_map":
                    mesh = eqn.params.get("mesh")
                    names = getattr(mesh, "axis_names", None) or ()
                    shard_axes = frozenset(names)
                stack.append(Frame(sub, frame, path, bindings, shard_axes,
                                   carries, _eqn_site(eqn, frame.path), uid[0]))


def iter_equations(jaxpr: Any) -> Iterator[Tuple[Any, Frame]]:
    """(eqn, frame) over the whole trace, one shared traversal."""
    for frame in walk_frames(jaxpr):
        for eqn in frame.jaxpr.eqns:
            yield eqn, frame


def trace(fn: Callable, *args: Any, **kwargs: Any) -> ClosedJaxpr:
    """Trace ``fn`` (args may be ShapeDtypeStructs; nothing executes)."""
    return jax.make_jaxpr(fn)(*args, **kwargs)


class CallCounter:
    """Wrap a block producer to count trace-time invocations.

    Replaces the hand-rolled counting-producer test idiom: wrap, trace,
    then hand ``counter.calls`` to :func:`dispatch_count`.
    """

    def __init__(self, fn: Callable):
        self.fn = fn
        self.calls = 0

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        self.calls += 1
        return self.fn(*args, **kwargs)


# --------------------------------------------------------------------------
# AvalBound
# --------------------------------------------------------------------------

def _aval_elements(var: Any) -> int:
    aval = getattr(var, "aval", None)
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0
    return int(np.prod(shape, dtype=np.int64)) if len(shape) else 1


def _aval_str(var: Any) -> str:
    aval = getattr(var, "aval", None)
    return str(getattr(aval, "str_short", lambda: aval)()) if aval is not None else "?"


def aval_bound(jaxpr: Any, budget: Optional[int] = None) -> Report:
    """Largest aval anywhere in the trace, against an element budget.

    Generalizes ``max_aval_elements`` into a reporting pass: the summary
    names the largest aval, its producing equation and source line, so a
    budget violation reads like a compiler diagnostic, not a number.
    """
    best = 0
    best_site: Optional[Site] = None
    best_aval = "?"
    for frame in walk_frames(jaxpr):
        jx = frame.jaxpr
        for var in (*jx.invars, *jx.constvars, *jx.outvars):
            n = _aval_elements(var)
            if n > best:
                best, best_site, best_aval = n, frame.origin_site, _aval_str(var)
        for eqn in jx.eqns:
            for var in (*eqn.invars, *eqn.outvars):
                n = _aval_elements(var)
                if n > best:
                    best, best_site, best_aval = n, _eqn_site(eqn, frame.path), _aval_str(var)
    report = Report("AvalBound", summary={
        "max_elements": best,
        "max_aval": best_aval,
        "at": str(best_site) if best_site is not None else "<toplevel>",
        "budget": budget,
    })
    if budget is not None and best > budget:
        report.violations.append(Violation(
            "AvalBound",
            f"largest aval {best_aval} has {best} elements > budget {budget}",
            best_site))
    return report


def jaxpr_max_elements(jaxpr: Any) -> int:
    """Largest aval (elements) anywhere in a (closed) jaxpr, recursively."""
    return int(aval_bound(jaxpr).summary["max_elements"])


# --------------------------------------------------------------------------
# DispatchCount
# --------------------------------------------------------------------------

def dispatch_count(jaxpr: Any,
                   max_top_level: Optional[int] = None,
                   producer_calls: Optional[int] = None,
                   max_producer_calls: Optional[int] = None) -> Report:
    """Count top-level dispatches and (optionally) producer invocations.

    A fused streamed pipeline is a *single* top-level equation (one
    ``jit``/``scan``); every extra top-level eqn is an extra device
    dispatch.  ``producer_calls`` comes from a :class:`CallCounter`
    wrapped around the block producer before tracing -- trace-time call
    count is the static number of producer inlinings.
    """
    jx = _as_jaxpr(jaxpr)
    per_prim: Dict[str, int] = {}
    boundaries = 0
    for eqn in jx.eqns:
        name = eqn.primitive.name
        per_prim[name] = per_prim.get(name, 0) + 1
        if name in DISPATCH_PRIMITIVES:
            boundaries += 1
    report = Report("DispatchCount", summary={
        "top_level_eqns": len(jx.eqns),
        "dispatch_boundaries": boundaries,
        "per_primitive": dict(sorted(per_prim.items())),
    })
    if producer_calls is not None:
        report.summary["producer_calls"] = producer_calls
    if max_top_level is not None and len(jx.eqns) > max_top_level:
        site = _eqn_site(jx.eqns[max_top_level], ())
        report.violations.append(Violation(
            "DispatchCount",
            f"{len(jx.eqns)} top-level equations > budget {max_top_level} "
            f"(first excess: {site.primitive})", site))
    if (max_producer_calls is not None and producer_calls is not None
            and producer_calls > max_producer_calls):
        report.violations.append(Violation(
            "DispatchCount",
            f"producer invoked {producer_calls}x at trace time "
            f"> budget {max_producer_calls}"))
    return report


# --------------------------------------------------------------------------
# KeyReuse
# --------------------------------------------------------------------------

def _is_key_var(var: Any) -> bool:
    aval = getattr(var, "aval", None)
    dtype = getattr(aval, "dtype", None)
    return dtype is not None and str(dtype).startswith("key")


def _param_fingerprint(params: Dict[str, Any]) -> str:
    parts = []
    for k in sorted(params):
        v = params[k]
        if isinstance(v, (Jaxpr, ClosedJaxpr)) or callable(v):
            continue
        try:
            parts.append(f"{k}={v!r}")
        except Exception:  # pragma: no cover - exotic param repr
            parts.append(f"{k}=<{type(v).__name__}>")
    return ";".join(parts)


class _KeyProvenance:
    """Structural backward-slice signatures for PRNG key operands.

    Two key operands with identical signatures were produced by the same
    static computation from the same roots -- consuming randomness from
    both is a key-reuse bug.  Signatures follow dataflow across frame
    boundaries (jit/scan-const operands bind through; scan carries and
    xs are per-loop opaque roots, so a single in-loop consumption of a
    per-iteration key slice is *not* flagged, while two distinct
    consumption sites of the same carried key are).
    """

    #: flag bits for the rootedness half of a signature
    CONST_KEY = 1  # slice reaches a key baked in as a trace constant
    FROM_ARG = 2   # slice reaches a top-level argument

    def __init__(self) -> None:
        # memo value: (signature, root-flags bitmask)
        self._memo: Dict[Tuple[int, Any], Tuple[str, int]] = {}

    def _h(self, *parts: str) -> str:
        return hashlib.sha1("\x1f".join(parts).encode()).hexdigest()[:16]

    def signature(self, frame: Frame, var: Any) -> Tuple[str, int]:
        if isinstance(var, Literal):
            return self._h("lit", repr(getattr(var, "val", None))), 0
        key = (frame.uid, var)
        hit = self._memo.get(key)
        if hit is not None:
            return hit
        pending = (self._h("cycle", str(frame.uid), str(var)), 0)
        self._memo[key] = pending
        eqn = frame.defs.get(var)
        if eqn is not None:
            out_idx = next((i for i, ov in enumerate(eqn.outvars) if ov is var), 0)
            parts = [eqn.primitive.name, str(out_idx),
                     _param_fingerprint(eqn.params)]
            flags = 0
            for iv in eqn.invars:
                s, f = self.signature(frame, iv)
                parts.append(s)
                flags |= f
            result = (self._h(*parts), flags)
        else:
            binding = frame.bindings.get(var)
            if binding is None:  # pragma: no cover - malformed jaxpr
                result = pending
            elif binding[0] == "var":
                _, parent, outer = binding
                result = self.signature(parent, outer)
            else:
                kind, scope, idx = binding
                flags = 0
                if kind == "arg":
                    flags |= self.FROM_ARG
                if kind == "const" and _is_key_var(var):
                    flags |= self.CONST_KEY
                result = (self._h(kind, str(scope), str(idx)), flags)
        self._memo[key] = result
        return result


def key_reuse(jaxpr: Any, allow_baked: bool = False) -> Report:
    """Prove every PRNG consumption draws from a distinct key fold.

    Collects each ``random_bits``/threefry consumption site, computes
    the backward-slice signature of its key operand, and flags (a) two
    distinct sites consuming identically-derived keys and (b) keys not
    derived from any traced key argument (baked randomness breaks
    draw-identity between placements).  ``allow_baked=True`` waives (b)
    for pipelines whose *matrix content* is procedurally generated from
    a seed (e.g. ``ImplicitBandedMatrix`` producers) -- content draws
    are data, not noise; the reuse check (a) still applies to them.
    """
    prov = _KeyProvenance()
    consumptions: List[Tuple[str, int, Site]] = []
    limit = sys.getrecursionlimit()
    sys.setrecursionlimit(max(limit, 50_000))
    try:
        for eqn, frame in iter_equations(jaxpr):
            if eqn.primitive.name not in RANDOM_CONSUMERS:
                continue
            n_keys = 2 if eqn.primitive.name == "threefry2x32" else 1
            sigs, flags = [], 0
            for iv in eqn.invars[:n_keys]:
                s, f = prov.signature(frame, iv)
                sigs.append(s)
                flags |= f
            consumptions.append(
                (prov._h(*sigs), flags, _eqn_site(eqn, frame.path)))
    finally:
        sys.setrecursionlimit(limit)
    by_sig: Dict[str, List[Site]] = {}
    for sig, _, site in consumptions:
        by_sig.setdefault(sig, []).append(site)
    baked = [site for _, flags, site in consumptions
             if (flags & _KeyProvenance.CONST_KEY)
             or not (flags & _KeyProvenance.FROM_ARG)]
    report = Report("KeyReuse", summary={
        "consumptions": len(consumptions),
        "distinct_keys": len(by_sig),
        "baked": len(baked),
    })
    for sig, sites in sorted(by_sig.items()):
        if len(sites) > 1:
            where = ", ".join(str(s) for s in sites)
            report.violations.append(Violation(
                "KeyReuse",
                f"{len(sites)} consumptions of identically-derived key "
                f"(sites: {where})", sites[0]))
    if not allow_baked:
        for site in baked:
            report.violations.append(Violation(
                "KeyReuse",
                "randomness not derived from any traced key argument "
                "(baked draws break placement draw-identity)", site))
    return report


# --------------------------------------------------------------------------
# PrecisionLint
# --------------------------------------------------------------------------

_SUB_F32 = ("float16", "bfloat16")


def _dtype_name(var: Any) -> Optional[str]:
    aval = getattr(var, "aval", None)
    dtype = getattr(aval, "dtype", None)
    return None if dtype is None else str(dtype)


def precision_lint(jaxpr: Any, allow_f64: bool = False) -> Report:
    """No silent f64 leaks; no sub-f32 accumulators where error compounds.

    Flags float64 avals anywhere (unless ``allow_f64``), float16 or
    bfloat16 scan/while carries (per-iteration rounding accumulates
    across the loop), and sub-f32 psum operands (cross-device reduction
    order makes low-precision sums placement-dependent).
    """
    report = Report("PrecisionLint", summary={})
    n_f64 = n_low_carry = n_low_psum = 0
    for frame in walk_frames(jaxpr):
        for var in frame.carries:
            name = _dtype_name(var)
            if name in _SUB_F32:
                n_low_carry += 1
                report.violations.append(Violation(
                    "PrecisionLint",
                    f"{name} loop carry {_aval_str(var)} (sub-f32 accumulator)",
                    frame.origin_site))
        for eqn in frame.jaxpr.eqns:
            for var in (*eqn.invars, *eqn.outvars):
                if not allow_f64 and _dtype_name(var) == "float64":
                    n_f64 += 1
                    report.violations.append(Violation(
                        "PrecisionLint",
                        f"float64 aval {_aval_str(var)} (silent f64 leak)",
                        _eqn_site(eqn, frame.path)))
            if eqn.primitive.name in COLLECTIVE_REDUCERS:
                for var in eqn.invars:
                    name = _dtype_name(var)
                    if name in _SUB_F32:
                        n_low_psum += 1
                        report.violations.append(Violation(
                            "PrecisionLint",
                            f"{name} psum operand {_aval_str(var)}",
                            _eqn_site(eqn, frame.path)))
    report.summary.update(f64_avals=n_f64, sub_f32_carries=n_low_carry,
                          sub_f32_psum_operands=n_low_psum)
    # de-duplicate repeated flags of the same var flowing through many eqns
    seen: set = set()
    unique: List[Violation] = []
    for v in report.violations:
        k = (v.message, str(v.site))
        if k not in seen:
            seen.add(k)
            unique.append(v)
    report.violations = unique
    return report


# --------------------------------------------------------------------------
# CollectiveAudit
# --------------------------------------------------------------------------

def collective_audit(jaxpr: Any,
                     allowed_axes: Optional[Sequence[str]] = None,
                     per_device_budget: Optional[int] = None) -> Report:
    """Audit collectives inside ``shard_map`` regions.

    ``psum`` reductions may only touch the declared row/col mesh axes,
    and no all-gather/all-to-all may move an operand larger than the
    per-device block budget -- an accidental gather of a sharded A is
    exactly how the scalability claim silently dies.
    """
    allowed = None if allowed_axes is None else frozenset(allowed_axes)
    report = Report("CollectiveAudit", summary={})
    n_psum = n_gather = 0
    for eqn, frame in iter_equations(jaxpr):
        name = eqn.primitive.name
        if frame.shard_axes is None:
            continue
        if name in COLLECTIVE_REDUCERS:
            n_psum += 1
            axes = tuple(a for a in (eqn.params.get("axes") or ())
                         if isinstance(a, str))
            if allowed is not None and not set(axes) <= allowed:
                extra = sorted(set(axes) - allowed)
                report.violations.append(Violation(
                    "CollectiveAudit",
                    f"psum over undeclared axes {extra} "
                    f"(allowed: {sorted(allowed)})",
                    _eqn_site(eqn, frame.path)))
        elif name in COLLECTIVE_GATHERS:
            n_gather += 1
            moved = max((_aval_elements(v) for v in (*eqn.invars, *eqn.outvars)),
                        default=0)
            if per_device_budget is not None and moved > per_device_budget:
                report.violations.append(Violation(
                    "CollectiveAudit",
                    f"{name} moves {moved} elements > per-device budget "
                    f"{per_device_budget}",
                    _eqn_site(eqn, frame.path)))
            elif per_device_budget is None:
                report.violations.append(Violation(
                    "CollectiveAudit",
                    f"{name} inside shard_map with no declared budget",
                    _eqn_site(eqn, frame.path)))
    report.summary.update(psums=n_psum, gathers=n_gather,
                          allowed_axes=sorted(allowed) if allowed else None)
    return report


# --------------------------------------------------------------------------
# convenience driver
# --------------------------------------------------------------------------

def run_all(jaxpr: Any, *,
            aval_budget: Optional[int] = None,
            max_top_level: Optional[int] = None,
            producer_calls: Optional[int] = None,
            max_producer_calls: Optional[int] = None,
            allowed_axes: Optional[Sequence[str]] = None,
            per_device_budget: Optional[int] = None,
            allow_f64: bool = False,
            allow_baked: bool = False) -> Dict[str, Report]:
    """Run all five passes over one trace; keyed by pass name."""
    return {
        "AvalBound": aval_bound(jaxpr, budget=aval_budget),
        "DispatchCount": dispatch_count(
            jaxpr, max_top_level=max_top_level,
            producer_calls=producer_calls,
            max_producer_calls=max_producer_calls),
        "KeyReuse": key_reuse(jaxpr, allow_baked=allow_baked),
        "PrecisionLint": precision_lint(jaxpr, allow_f64=allow_f64),
        "CollectiveAudit": collective_audit(
            jaxpr, allowed_axes=allowed_axes,
            per_device_budget=per_device_budget),
    }
