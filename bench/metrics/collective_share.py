"""collective_share.<family>: the share of the devices' busy time, in %,
spent in collective operations, from the device trace: the psum of tier-1
partials over the mesh's contraction axis, the reshard of each CG iterate
between the row and the column sharding, and the solver's cross-chip dot
products.  Nothing is read where no collective ran (every one-chip cell)."""

# A collective op in the trace, as ``trace.read_xplane`` names it
# (``%all-reduce.3 all-reduce``, ``%psum.23 all-reduce``): matched by its
# opcode, synchronous or the ``-start``/``-done`` halves of an asynchronous
# one, or by a fusion the compiler named after one
# (``%all-reduce-fusion.1 fusion``).
COLLECTIVES = r"(all-reduce|all-gather|collective-permute|all-to-all|reduce-scatter)"
PATTERN = (rf"^%\S+ {COLLECTIVES}(-start|-done)?$"
           rf"|^%{COLLECTIVES}(-start|-done)?([.\-]\S*)? fusion$")


def read(run):
    if run.trace is None:
        return {}
    share = run.reduce.busy_share_of(run.trace, PATTERN)
    if share is None:
        return {}
    return {f"collective_share.{run.family}": 100.0 * share}
