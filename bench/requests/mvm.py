"""mvm: one corrected MVM of a request's input panel.  The system and the
reference each expose ``mvm(x, key)`` (and ``tenant=`` where the mix names
tenants), returning the product as a device array."""
import jax

FAMILY = "mvm"       # the suffix of this kind's metric variants
ANSWER = "y"         # what the check compares


def send(system, req, params):
    kw = {} if req.tenant is None else {"tenant": req.tenant}
    return system.mvm(req.x, req.key, **params, **kw)


def receive(sent):
    return jax.block_until_ready(sent), {"mvms": 1}
