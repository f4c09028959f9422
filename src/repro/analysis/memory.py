"""Static allocation analysis: the largest array a traced program can hold.

The paper's scalability claim is that a >= 65,536^2 solve never allocates an
A-sized array -- not on the host, not on any device.  That property is
*structural*: it is visible in the jaxpr of the jitted computation before
anything runs.  :func:`max_aval_elements` walks every equation (recursing
into scan/while/cond/jit/shard_map/custom_vjp sub-jaxprs) and returns the
largest intermediate, input, constant or output aval in elements, so tests
and benchmarks can assert ``max_aval_elements(mvm_fn, x, key) << m * n``
without paying for (or being able to afford) a real A-sized buffer.

Note the per-device view: inside a ``shard_map`` sub-jaxpr the avals are the
per-device block shapes, which is exactly the bound that matters -- a global
array sharded 8 ways shows up as its (A/8)-sized local aval, while a true
A-sized materialization shows up full size on the offending equation.

The traversal itself lives in :mod:`repro.analysis.verify` -- the shared
IR walker behind every invariant pass -- so there is exactly one
implementation of sub-jaxpr discovery.  (The original walker here missed
jaxprs reached through dict or nested-container params and the
``custom_vjp`` forward rule hidden behind ``fwd_jaxpr_thunk``; see
tests/test_verify.py::TestWalkerRegressions for the known-bad programs.)
For a reporting variant that also names the offending equation and source
line, use :func:`repro.analysis.verify.aval_bound`.
"""
from __future__ import annotations

from typing import Any

import jax

from repro.analysis.verify import jaxpr_max_elements

__all__ = ["max_aval_elements", "jaxpr_max_elements"]


def max_aval_elements(fn, *args: Any, **kwargs: Any) -> int:
    """Largest array (in elements) the traced ``fn(*args)`` can ever hold.

    ``args`` may be real arrays or ``jax.ShapeDtypeStruct`` placeholders --
    nothing executes and nothing is allocated; only the trace is inspected.
    """
    return jaxpr_max_elements(jax.make_jaxpr(fn)(*args, **kwargs))
