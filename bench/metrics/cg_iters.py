"""cg_iters: CG iterations per solve, the mean of the counts the solver
returns (``SolveResult.iterations``) over the window's solves."""


def read(run):
    its = [r["iterations"] for r in run.records if "iterations" in r]
    if run.family != "solve" or not its:
        return {}
    return {"cg_iters": sum(its) / len(its)}
