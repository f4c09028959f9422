"""solve: one solve of a request's right-hand sides from x0 = 0, by the
solver, tolerance and iteration cap of the mix's ``params``.  The system
and the reference each expose ``solve(b, key, *, solver, tol, maxiter)``
(and ``tenant=`` where the mix names tenants), returning ``(x,
iterations, mvms)`` as device values."""
import jax

FAMILY = "solve"     # the suffix of this kind's metric variants
ANSWER = "x"         # what the check compares


def send(system, req, params):
    kw = {} if req.tenant is None else {"tenant": req.tenant}
    return system.solve(req.x, req.key, **params, **kw)


def receive(sent):
    """Wait for the answer; the counters stay on the device (the harness
    reads them once the window has closed)."""
    x, iterations, mvms = sent
    jax.block_until_ready(x)
    return x, {"iterations": iterations, "mvms": mvms}
