"""solve_ms: the window's wall time over the solves it completed (host
clock); each solve ran to its tolerance and was waited for."""


def read(run):
    if run.family != "solve" or not run.records:
        return {}
    return {"solve_ms": 1e3 * run.window_s / len(run.records)}
