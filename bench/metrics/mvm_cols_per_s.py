"""mvm_cols_per_s: corrected-MVM input columns answered over the window's
wall time (host clock)."""


def read(run):
    if run.family != "mvm" or not run.records:
        return {}
    return {"mvm_cols_per_s": sum(r["cols"] for r in run.records)
            / run.window_s}
